"""Builder's tool (chip): drives cells through the harness's own
`run_cell` with what a measured run may never take: another rate (the
knee sweep), the program's own lower-precision path or the bfloat16
reference in the program's place (the controls), a planted fault.  Several
seeds and rates in ONE process, so that set-up's cache loads are paid
once.  One JSON line per run on standard output; never a measurement of
the benchmark.

    python benchmark/tools/probe.py --workload <cell> --seeds 1,2,3 \
        --seconds 20 [--rates 16,20,24] [--program-dtype bfloat16] \
        [--fault token_swap] [--control 1] [--trace 0] [--rehearse 0]

--rates            offer these rates instead of the mix's (open_loop)
--program-dtype    run the program with this compute_dtype: its own
                   lower path, judged by the cell's limits against the
                   same reference (the control of "How correct is decided")
--control 1        also put the bfloat16 reference in the program's place
                   on the same tokens or batches
--fault token_swap the best candidate's token swapped for the second's
                   where the slot step produces it (a served cell)
--fault half_batch the reference over half of each batch in the
                   program's place (a training cell)
--beam-look 1      for each search of a served cell's beam sample: where
                   the served tokens and the reference's own best part,
                   and how close the pruning was there (PERF.md section 6)
"""
import argparse
import copy
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import run as bench_run  # noqa: E402


def plant_token_swap(model_family: str):
    import importlib

    import jax.numpy as jnp

    m = importlib.import_module(
        "textsummarization_on_flink_tpu.models." + model_family)

    real = m.beam_adapter_masked

    def broken(hps):
        init, step = real(hps)

        def bad_step(*args, **kw):
            out = step(*args, **kw)
            ids = out.topk_ids
            ids = ids.at[:, 0].set(out.topk_ids[:, 1]).at[:, 1].set(
                out.topk_ids[:, 0])
            return out._replace(topk_ids=jnp.asarray(ids))
        return init, bad_step

    m.beam_adapter_masked = broken


def beam_look(cfg, seed, finished, words, sample):
    """For each search of the beam sample: the gap, the step at which the
    served tokens and the reference's best part (`split_at`), the step at
    which the reference's search drops the served prefix (`left_at`) with
    the total log probability by which the last kept candidate beat it
    there, and `closest_call`: up to the parting, the least total log
    probability by which the candidate that ended a pruning (the one that
    filled the beam or the results) stood off its neighbours in the
    ranking whose swap with it would have pruned otherwise, and its step.
    Totals, not length-normalised: `score_gap` times the length says how
    far the program's total may lie from the reference's."""
    from harness import correct, weights
    from harness import reference as ref

    hp = cfg["hparams"]
    fam = ref.family(cfg["family"])
    params = weights.make_params(cfg, seed)
    picked = correct.pick_sample(finished, int(sample["score"]),
                                 seed)[:int(sample["beam"])]
    served = [correct.served_tokens(words, a, r, hp) for a, r in picked]
    r_avg = ref.score_tokens(fam, params, hp, [(a.ids, a.ext)
                                               for a, _ in picked],
                             [t for t, _ in served])
    looks = []
    for (a, _), (toks, n), total in zip(picked, served, r_avg):
        seen = []
        best, best_avg = ref.beam_search(
            fam, params, hp, a.ids, a.ext,
            on_step=lambda t, ranked, kept, results: seen.append(
                (t, list(ranked), [h[0] for h in kept + results])))
        split = next((i for i, (x, y) in enumerate(zip(toks, best))
                      if x != y), None)
        if split is None and len(toks) != len(best):
            split = min(len(toks), len(best))
        look = {"gap": float(best_avg - total / n), "served_len": len(toks),
                "best_len": len(best), "split_at": split, "left_at": None,
                "closest_call": None}
        for t, ranked, kept in seen:
            cut = max(i for i, h in enumerate(ranked) if h[0] in kept)
            mine = [h for h in ranked if h[0] == toks[:t + 1]]
            if look["left_at"] is None and toks[:t + 1] not in kept \
                    and t < len(toks):
                look["left_at"] = t
                look["kept_over_served"] = (
                    float(ranked[cut][1] - mine[0][1]) if mine else None)
            if split is not None and t > split:
                continue
            stop = [h[0][-1] == ref.STOP_ID for h in ranked]
            calls = []
            if cut + 1 < len(ranked):
                calls.append(ranked[cut][1] - ranked[cut + 1][1])
            if cut > 0 and stop[cut - 1] != stop[cut]:
                calls.append(ranked[cut - 1][1] - ranked[cut][1])
            if calls and (look["closest_call"] is None
                          or min(calls) < look["closest_call"][0]):
                look["closest_call"] = (float(min(calls)), t)
        looks.append(look)
    return looks


def half_p50(run, traffic):
    half = run.window_s / 2
    out = {}
    for tag, part in (
            ("first", [l for l, d in zip(run.latencies_ms, run.due_s)
                       if d < half]),
            ("second", [l for l, d in zip(run.latencies_ms, run.due_s)
                        if d >= half])):
        if part:  # a backlog that grows shows as a later half slower
            out[f"p50_ms_{tag}_half"] = traffic.percentile(part, 50)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--program-dtype", default="")
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--fault", default="")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--beam-look", type=int, default=0)
    ap.add_argument("--pair", type=int, default=0,
                    help="1: seed i goes with rate i, not with every rate")
    args = ap.parse_args()

    bench, cell, cfg, mix, cell_file = bench_run.load_cell(args.workload)
    if args.rehearse:
        bench_run.apply_rehearsal(cfg, mix, cell_file)
    bench_run.prepare_process(args.rehearse, args.trace)
    from harness import correct, traffic, weights

    if args.program_dtype:
        cfg["hparams"]["compute_dtype"] = args.program_dtype
    if args.fault == "token_swap":
        plant_token_swap(cfg["hparams"]["model_family"])
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    seeds = [int(s) for s in args.seeds.split(",")]
    fam, clock = weights.summary_clock(cfg)
    for i, seed in enumerate(seeds):
        for rate in ([rates[i % len(rates)]] if args.pair else rates):
            m = copy.deepcopy(mix)
            if rate is not None:
                m["rate_per_s"] = rate
            out = bench_run.run_cell(bench, cell, copy.deepcopy(cfg), m,
                                     cell_file, seed, args.seconds,
                                     args.trace, args.rehearse)
            line, run = out["line"], out["run"]
            rec = {"workload": args.workload, "seed": seed, "rate": rate,
                   "program_dtype": args.program_dtype or "float32",
                   "fault": args.fault, "seconds": args.seconds,
                   "correct": line["correct"], "compared": line["compared"],
                   "attempted": line["attempted"], "failed": line["failed"],
                   "e2e": out["e2e"], "harness": out["harness"],
                   "detail": out["detail"], "programs": out["programs"],
                   "metrics": line.get("metrics"),
                   "device": line.get("device")}
            if mix["kind"] == "open_loop":
                rec["completed_per_s"] = run.completed_in_window / run.window_s
                rec.update(half_p50(run, traffic))
                lens = sorted(len(r.decoded_words) + 1 for _, r in run.finished)
                rec["summary_tokens"] = {
                    "min": lens[0], "p25": lens[len(lens) // 4],
                    "p50": lens[len(lens) // 2],
                    "p75": lens[3 * len(lens) // 4], "max": lens[-1]} \
                    if lens else None
                if clock:
                    off = [len(r.decoded_words) + 1 - int(fam.length_code(
                        clock, int(a.ids[0]))) for a, r in run.finished
                        if len(r.decoded_words) < cfg["hparams"][
                            "max_dec_steps"]]
                    if off:
                        rec["served_minus_coded_tokens"] = {
                            "mean": sum(off) / len(off), "min": min(off),
                            "max": max(off)}
            if args.beam_look:
                rec["beam_look"] = beam_look(
                    cfg, seed, run.finished, out["words"],
                    cell_file["check"]["sample"])
            extra = []
            if args.control:
                extra.append(("control_bf16_reference", True))
            if args.fault == "half_batch":
                extra.append(("fault_half_batch", "half_batch"))
            for key, how in extra:
                numbers = out["read_numbers"](control=how)
                numbers.pop("_detail")
                numbers["compiles_in_window"] = 0
                ok, compared = correct.judge(numbers, cell_file["limits"])
                rec[key] = {"correct": ok, "compared": {
                    k: [v["value"], v["limit"]] for k, v in compared.items()}}
            print(json.dumps(rec, default=float), flush=True)


if __name__ == "__main__":
    main()
