#!/usr/bin/env python
"""Roofline lower bounds for the train configs, from XLA's own cost
model.

    python scripts/roofline.py [--configs train_b16,train_b64,...]
                               [--chip v5e] [--json]
                               [--bench ROWS.jsonl]

For each config this compiles the REAL train step on the current
backend (CPU works: HLO flop counts are backend-portable; bytes
accessed depends on fusion decisions, so treat it as an estimate) and
reports:

  * flops/step from XLA `cost_analysis()` next to the analytic model
    `bench.py` uses for MFU (a big disagreement means one of them is
    wrong — that cross-check is the point of printing both);
  * bytes accessed/step and arithmetic intensity;
  * the compute floor (flops / peak bf16) and bandwidth floor
    (bytes / peak HBM) on the target chip, whichever is larger being
    the minimum achievable step time, with the implied max samples/s;
  * with `--bench FILE` (a JSONL of bench.py rows tagged by
    BENCH_RUN_TAG), the measured step time of the newest row whose run
    tag matches (measured/floor says how much of the gap is left for
    dispatch latency and scan overhead).

Why it exists: an MFU number alone ("3.1%") reads as an indictment; the
roofline says how much of that is physics.  E.g. at reference scale the
pointer-generator step accesses ~12 GB — a ~15 ms bandwidth floor on
one v5e regardless of FLOPs — so the remaining levers (unroll, bf16
streams) attack bytes and scan latency, not FLOPs.

The reference has no counterpart: its only instrumentation is per-step
wall clock (run_summarization.py:223-226) on a CPU-pinned graph.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# per-chip (peak bf16 TFLOP/s, peak HBM GB/s) — public TPU specs
CHIPS = {
    "v4": (275.0, 1228.0),
    "v5e": (197.0, 819.0),
    "v5p": (459.0, 2765.0),
    "v6e": (918.0, 1640.0),
}

# row tag -> the bench.py env that measures it; the actual shapes come
# from bench._preset_overrides via hps_for(), so the roofline always
# describes exactly the config the bench measures (no hand-duplicated
# values to drift).  train_tiny exists for fast tests
# (unroll=1: tracing cost scales with the unrolled scan body; the
# flop/byte counts are unroll-invariant).
CONFIGS = {
    "train_b16": {},
    "train_b16_remat": {"BENCH_REMAT": "1"},
    "train_b64": {"BENCH_BATCH": "64"},
    "train_scaled": {"BENCH_PRESET": "scaled"},
    "train_transformer": {"BENCH_FAMILY": "transformer"},
    "train_tiny": {"BENCH_PRESET": "tiny", "BENCH_BATCH": "4",
                   "BENCH_UNROLL": "1"},
    # byte-diet lever rows (ISSUE 5, PERF.md "Byte diet"): streaming
    # chunked vocab loss, bf16 optimizer state, and both together — the
    # roofline's bytes column is the CPU-verifiable side of each claim
    "train_b16_losschunk": {"BENCH_LOSS_CHUNK": "25"},
    "train_b16_optbf16": {"BENCH_OPT_DTYPE": "bfloat16"},
    "train_b16_bytediet": {"BENCH_LOSS_CHUNK": "25",
                           "BENCH_OPT_DTYPE": "bfloat16"},
    "train_transformer_losschunk": {"BENCH_FAMILY": "transformer",
                                    "BENCH_LOSS_CHUNK": "25"},
}

# decode lever configs (ISSUE 7, PERF.md "Decode byte diet"): the
# compiled beam search's bytes per emitted token + peak temp via
# __graft_entry__.decode_step_cost — batch path (the auto 'chunked'
# loop) and one step_slots_jit slot chunk per family, plus a tiny row
# for the repro smoke.  The committed gate-scale reductions live in
# BYTE_BUDGET.json's decode section; these rows put the ask-scale
# numbers in the sweep record like the train lever rows above.
DECODE_CONFIGS = {
    "decode_bytes_pg": {"env": {}, "path": "batch"},
    "decode_bytes_pg_slot": {"env": {}, "path": "slot"},
    "decode_bytes_transformer": {"env": {"BENCH_FAMILY": "transformer"},
                                 "path": "batch"},
    "decode_bytes_transformer_slot": {
        "env": {"BENCH_FAMILY": "transformer"}, "path": "slot"},
    "decode_bytes_tiny": {"env": {"BENCH_PRESET": "tiny",
                                  "BENCH_BATCH": "2", "BENCH_UNROLL": "1"},
                          "path": "batch"},
}

# speculative-tier FLOPs rows (ISSUE 10, PERF.md "Speculative tier"):
# per-tier FLOPs per emitted token via __graft_entry__.decode_step_flops
# (beam / greedy / AAN draft, plus the transformer's parallel verify) —
# the draft-cost side of BYTE_BUDGET.json's spec section at ask scale.
SPEC_CONFIGS = {
    "spec_flops_pg": {"env": {}},
    "spec_flops_transformer": {"env": {"BENCH_FAMILY": "transformer"}},
}

_BENCH_ENV_VARS = ("BENCH_BATCH", "BENCH_PRESET", "BENCH_FAMILY",
                   "BENCH_UNROLL", "BENCH_REMAT", "BENCH_LOSS_CHUNK",
                   "BENCH_OPT_DTYPE")

# lever row -> the config its byte reduction is measured against
_BYTE_DIET_BASELINES = {
    "train_b16_losschunk": "train_b16",
    "train_b16_optbf16": "train_b16",
    "train_b16_bytediet": "train_b16",
    "train_transformer_losschunk": "train_transformer",
}


def hps_for(tag: str, bench_mod):
    """The exact HParams the tagged bench row measures: the tag's env
    mapping + bench.bench_train's own construction."""
    from textsummarization_on_flink_tpu.config import HParams

    if tag in DECODE_CONFIGS:
        env = DECODE_CONFIGS[tag]["env"]
    elif tag in SPEC_CONFIGS:
        env = SPEC_CONFIGS[tag]["env"]
    else:
        env = CONFIGS[tag]
    saved = {k: os.environ.pop(k, None) for k in _BENCH_ENV_VARS}
    try:
        os.environ.update(env)
        batch = int(os.environ.get("BENCH_BATCH", "16"))
        hps = HParams(batch_size=batch, compute_dtype="bfloat16",
                      **bench_mod._preset_overrides())
        if tag in SPEC_CONFIGS:
            # the committed REFERENCE-scale draft recipe (BYTE_BUDGET.json
            # spec.ref_overrides: 1 kept layer, H/2-wide narrow draft,
            # rank-64 factored head — ISSUE 12), spec_k from the HParams
            # default; read from the budget so this row and the gate can
            # never describe two different drafts
            budget_path = os.path.join(REPO, "BYTE_BUDGET.json")
            with open(budget_path, encoding="utf-8") as f:
                ref_overrides = json.load(f)["spec"]["ref_overrides"]
            return hps.replace(mode="decode", **ref_overrides)
        return hps.replace(mode="decode") if tag in DECODE_CONFIGS else hps
    finally:
        for k, v in saved.items():
            os.environ.pop(k, None)
            if v is not None:
                os.environ[k] = v


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("bench", mod)
    spec.loader.exec_module(mod)
    return mod


def cost_of_train_step(hps):
    """Compile the real train step and return XLA's {flops, bytes,
    temp_bytes} — through the ONE shared compile-and-read helper
    (__graft_entry__.train_step_cost), same as bench.py's bytes mode and
    the tier-1 byte gate."""
    from __graft_entry__ import train_step_cost

    return train_step_cost(hps)


def analyze(tag: str, chip: str, bench_mod, measured: dict | None):
    hps = hps_for(tag, bench_mod)
    cost = cost_of_train_step(hps)
    analytic = (bench_mod.transformer_flops_per_step(hps)
                if hps.model_family == "transformer"
                else bench_mod.train_flops_per_step(hps))
    peak_tflops, peak_gbps = CHIPS[chip]
    t_compute = cost["flops"] / (peak_tflops * 1e12)
    t_bw = cost["bytes"] / (peak_gbps * 1e9)
    floor = max(t_compute, t_bw)
    rec = {
        "config": tag,
        "chip": chip,
        "batch": hps.batch_size,
        "xla_flops": cost["flops"],
        "analytic_flops": analytic,
        "flops_ratio_xla_over_analytic": round(cost["flops"] / analytic, 2),
        "bytes_accessed": cost["bytes"],
        "arith_intensity_flops_per_byte": round(
            cost["flops"] / max(cost["bytes"], 1.0), 2),
        "compute_floor_ms": round(t_compute * 1e3, 3),
        "bandwidth_floor_ms": round(t_bw * 1e3, 3),
        "min_step_ms": round(floor * 1e3, 3),
        "bound": "bandwidth" if t_bw >= t_compute else "compute",
        "max_samples_per_sec": round(hps.batch_size / floor, 1),
    }
    if measured is not None:
        ms = measured.get("step_time_ms")
        if ms:
            rec["measured_step_ms"] = ms
            rec["measured_over_floor"] = round(ms / rec["min_step_ms"], 2)
            rec["measured_at"] = measured.get("captured_at")
    return rec


def analyze_decode(tag: str, chip: str, bench_mod):
    """A decode-bytes row: bytes/token + peak temp of the compiled beam
    search, with the chip's bandwidth floor per emitted token (the
    decode analogue of the train rows' min_step_ms)."""
    from textsummarization_on_flink_tpu.config import beam_chunk_from_env
    from __graft_entry__ import decode_step_cost

    hps = hps_for(tag, bench_mod)
    path = DECODE_CONFIGS[tag]["path"]
    chunk = min(beam_chunk_from_env(), hps.max_dec_steps)
    cost = decode_step_cost(hps, loop="chunked" if path == "batch" else "scan",
                            chunk=chunk, path=path)
    _, peak_gbps = CHIPS[chip]
    t_bw_token = cost["bytes_per_token"] / (peak_gbps * 1e9)
    return {
        "config": tag,
        "chip": chip,
        "path": path,
        "batch": hps.batch_size,
        "family": hps.model_family,
        "chunk": chunk,
        "bytes_accessed": cost["bytes"],
        "bytes_per_token": round(cost["bytes_per_token"], 1),
        "temp_bytes": cost["temp_bytes"],
        "bandwidth_floor_us_per_token": round(t_bw_token * 1e6, 3),
        "max_tokens_per_sec": round(1.0 / max(t_bw_token, 1e-12), 1),
        "note": "HloCostAnalysis single-counts the decode loop body, so "
                "bytes/token tracks per-step traffic + loop-invariant "
                "overhead; committed gate-scale reductions live in "
                "BYTE_BUDGET.json decode",
    }


def analyze_spec(tag: str, chip: str, bench_mod):
    """A spec-tier FLOPs row: per-tier step FLOPs per emitted token
    (cost-analysis + the closed-form analytic model), the draft/full
    ratio, and the acceptance->expected-speedup curve the committed
    BYTE_BUDGET.json spec section models."""
    from __graft_entry__ import decode_step_flops

    hps = hps_for(tag, bench_mod)
    peak_tflops, _ = CHIPS[chip]
    flops = decode_step_flops(hps)
    tiers = {
        name: {
            "flops_per_token": c["flops"],
            "analytic_flops_per_token": c["analytic_flops"],
            "state_bytes": c["state_bytes"],
            "compute_floor_us_per_token": round(
                c["flops"] / (peak_tflops * 1e12) * 1e6, 4),
        }
        for name, c in flops["tiers"].items()
    }
    return {
        "config": tag,
        "chip": chip,
        "family": hps.model_family,
        "spec_k": flops["spec_k"],
        "draft_dec_layers": hps.draft_dec_layers or hps.dec_layers,
        "tiers": tiers,
        "draft_full_flops_ratio": round(flops["draft_full_ratio"], 4),
        "draft_state_ratio": round(flops["draft_state_ratio"], 4),
        "verify_flops_per_position": flops["verify_flops_per_position"],
        "expected_speedup_vs_acceptance": {
            a: round(s, 4) for a, s in flops["expected_speedup"].items()},
        "note": "speedup model: one verify invocation ~ one full step "
                "(bandwidth-bound weight streaming); committed ceilings "
                "+ kill conditions in BYTE_BUDGET.json spec",
    }


def _cost_of(fn, *args):
    import jax

    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def attribution_of(hps, full_step_cost=None):
    """Where the step's bytes go, by phase: compile forward-only and
    forward+backward, and diff against the full optimizer step —
    backward = grad − forward, optimizer = step − grad.  Pass the
    already-compiled full-step cost (analyze() has it) to avoid
    recompiling the most expensive program.

    Caveat on every (diff) row's BYTES: each phase is an independently
    compiled program, and a standalone subprogram must materialize
    outputs the bigger program may fuse away — so a diff can come out
    low or even negative when fusion overlaps phases.  Flop diffs don't
    have this problem (flop counts are fusion-independent).  The table
    marks negative byte diffs as fusion overlap."""
    import numpy as np

    import jax

    from textsummarization_on_flink_tpu.models import get_family
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib
    from __graft_entry__ import _example_arrays

    family = get_family(hps.model_family)
    state = trainer_lib.init_train_state(hps, hps.vocab_size, seed=0)
    arrays = _example_arrays(hps, np.random.RandomState(0))

    def fwd(params, arrays):
        out = family.forward_train(params, hps, arrays)
        return out.total_loss if hps.coverage else out.loss

    if full_step_cost is None:
        full_step_cost = cost_of_train_step(hps)
    phases = {
        "forward": _cost_of(fwd, state.params, arrays),
        "fwd+bwd": _cost_of(lambda p, a: jax.grad(fwd)(p, a),
                            state.params, arrays),
        "full step": dict(full_step_cost),
    }
    if hps.model_family == "pointer_generator":
        # the pg family has a clean encoder seam (models.pointer_generator
        # .encode); the remainder of forward is the decoder scan + the
        # vocab projection + loss
        from textsummarization_on_flink_tpu.models import (
            pointer_generator as pg,
        )

        enc = _cost_of(
            lambda p, a: pg.encode(p, hps, a["enc_batch"], a["enc_lens"],
                                   a["enc_padding_mask"]),
            state.params, arrays)
        phases["encoder fwd"] = enc
        phases["dec+loss fwd (diff)"] = {
            k: phases["forward"][k] - enc[k] for k in ("flops", "bytes")}
    phases["backward (diff)"] = {
        k: phases["fwd+bwd"][k] - phases["forward"][k]
        for k in ("flops", "bytes")}
    phases["optimizer (diff)"] = {
        k: phases["full step"][k] - phases["fwd+bwd"][k]
        for k in ("flops", "bytes")}
    return phases


def measured_rows(path) -> dict:
    """Newest measurement per run tag in a JSONL of bench.py rows (rows
    with an "error" field are not measurements); {} without a file."""
    if not path:
        return {}
    best: dict = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if (not isinstance(rec, dict) or "run" not in rec
                    or "error" in rec):
                continue
            prev = best.get(rec["run"])
            # captured_at is ISO-8601 UTC (lexicographic == time order);
            # ties fall back to file order
            if prev is None or str(rec.get("captured_at", "")) >= \
                    str(prev.get("captured_at", "")):
                best[rec["run"]] = rec
    return best


def main(argv=None):
    ap = argparse.ArgumentParser()
    default_cfgs = ("train_b16,train_b16_remat,train_b64,train_scaled,"
                    "train_transformer,train_b16_losschunk,"
                    "train_b16_optbf16,train_b16_bytediet,"
                    "train_transformer_losschunk,"
                    "decode_bytes_pg,decode_bytes_pg_slot,"
                    "decode_bytes_transformer,decode_bytes_transformer_slot,"
                    "spec_flops_pg,spec_flops_transformer")
    ap.add_argument("--configs", default=default_cfgs)
    ap.add_argument("--chip", default="v5e", choices=sorted(CHIPS))
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--bench", default=None,
                    help="JSONL of bench.py rows to join measured step "
                         "times from (no default: absent = no join)")
    ap.add_argument("--attribute", action="store_true",
                    help="also compile forward and fwd+bwd per config "
                         "(full-step cost is reused) and report the "
                         "per-phase flop/byte split")
    args = ap.parse_args(argv)

    bench_mod = _load_bench()
    measured = measured_rows(args.bench)
    out = []
    decode_out = []
    spec_out = []
    for tag in args.configs.split(","):
        tag = tag.strip()
        if tag in DECODE_CONFIGS:
            print(f"[roofline] compiling {tag} ...", file=sys.stderr)
            decode_out.append(analyze_decode(tag, args.chip, bench_mod))
            continue
        if tag in SPEC_CONFIGS:
            print(f"[roofline] compiling {tag} ...", file=sys.stderr)
            spec_out.append(analyze_spec(tag, args.chip, bench_mod))
            continue
        if tag not in CONFIGS:
            raise SystemExit(f"unknown config {tag!r}; "
                             f"choose from {sorted(CONFIGS)}, "
                             f"{sorted(DECODE_CONFIGS)}, or "
                             f"{sorted(SPEC_CONFIGS)}")
        print(f"[roofline] compiling {tag} ...", file=sys.stderr)
        rec = analyze(tag, args.chip, bench_mod, measured.get(tag))
        if args.attribute:
            rec["attribution"] = attribution_of(
                hps_for(tag, bench_mod),
                full_step_cost={"flops": rec["xla_flops"],
                                "bytes": rec["bytes_accessed"]})
        out.append(rec)
    if args.json:
        for rec in out + decode_out + spec_out:
            print(json.dumps(rec))
        return 0
    hdr = (f"{'config':<18} {'bound':<9} {'GFLOP':>8} {'GB':>7} "
           f"{'floor ms':>8} {'max smp/s':>9} {'measured':>9}")
    print(f"roofline on one {args.chip} "
          f"({CHIPS[args.chip][0]:.0f} bf16 TFLOP/s, "
          f"{CHIPS[args.chip][1]:.0f} GB/s HBM)")
    if out:
        print(hdr)
    for r in out:
        meas = (f"{r['measured_step_ms']:.1f}ms"
                if "measured_step_ms" in r else "-")
        print(f"{r['config']:<18} {r['bound']:<9} "
              f"{r['xla_flops'] / 1e9:>8.1f} "
              f"{r['bytes_accessed'] / 1e9:>7.2f} "
              f"{r['min_step_ms']:>8.2f} "
              f"{r['max_samples_per_sec']:>9.0f} {meas:>9}")
    if decode_out:
        print("\ndecode byte accounting (loop body single-counted; "
              "committed reductions in BYTE_BUDGET.json decode):")
        print(f"{'config':<30} {'path':<6} {'KB/token':>9} "
              f"{'peak temp MB':>13} {'floor us/tok':>13}")
        for r in decode_out:
            temp = (f"{r['temp_bytes'] / 1e6:.1f}"
                    if r["temp_bytes"] is not None else "-")
            print(f"{r['config']:<30} {r['path']:<6} "
                  f"{r['bytes_per_token'] / 1e3:>9.1f} {temp:>13} "
                  f"{r['bandwidth_floor_us_per_token']:>13.3f}")
    if spec_out:
        print("\nspeculative-tier FLOPs per emitted token "
              "(committed ceilings in BYTE_BUDGET.json spec):")
        print(f"{'config':<24} {'tier':<7} {'kFLOP/tok':>10} "
              f"{'analytic':>9} {'state B':>8}")
        for r in spec_out:
            for name in ("beam", "greedy", "draft"):
                t = r["tiers"][name]
                print(f"{r['config']:<24} {name:<7} "
                      f"{t['flops_per_token'] / 1e3:>10.1f} "
                      f"{t['analytic_flops_per_token'] / 1e3:>9.1f} "
                      f"{t['state_bytes']:>8}")
            curve = ", ".join(
                f"a={a}:{s:.2f}" for a, s in
                r["expected_speedup_vs_acceptance"].items())
            print(f"  draft/full ratio {r['draft_full_flops_ratio']:.3f} "
                  f"(state {r['draft_state_ratio']:.4f}); "
                  f"expected speedup {curve}")
    by_tag = {r["config"]: r for r in out}
    diet_rows = [(tag, base) for tag, base in _BYTE_DIET_BASELINES.items()
                 if tag in by_tag and base in by_tag]
    if diet_rows:
        print("\nbyte-diet reductions (bytes accessed vs baseline config):")
        for tag, base in diet_rows:
            red = 1.0 - (by_tag[tag]["bytes_accessed"]
                         / by_tag[base]["bytes_accessed"])
            print(f"  {tag:<28} vs {base:<18} {red * 100:>6.1f}%")
    for r in out:
        if "attribution" in r:
            print(f"\n{r['config']} phase split (GB accessed / GFLOP):")
            for phase, c in r["attribution"].items():
                note = ("  [negative: fusion overlap between standalone-"
                        "compiled phases]" if c["bytes"] < 0 else "")
                print(f"  {phase:<17} {c['bytes'] / 1e9:>7.2f} GB  "
                      f"{c['flops'] / 1e9:>8.1f} GFLOP{note}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
