"""Benchmark harness: pointer-generator throughput/latency/MFU on TPU.

The reference publishes no numbers; its train loop is instrumented but
CPU-bound TF1 (graph pinned to /cpu:0, model.py:313, per-step timing at
run_summarization.py:223-226).  The operative anchor is the See et al.
setup the pretrained checkpoint came from: 230k iterations at batch 16
in "3 days 4 hours" on a single Tesla K40m GPU (pointer-generator
README) = 0.84 steps/s = 13.5 samples/sec — that is the `vs_baseline`
denominator for training throughput.

Prints ONE JSON line on stdout, e.g.
  {"metric": "train_samples_per_sec", "value": N, "unit": "samples/s",
   "vs_baseline": N, "mfu": M, "platform": ..., "device": ...,
   "device_count": ...}

The default entry is a SUPERVISOR that re-execs this file as a child
process with a bounded per-attempt timeout; the child (TS_BENCH_CHILD=1)
does the real work.  The supervisor never touches jax — a chip belongs
to one process at a time, and that process is the child.  A run with no
result prints one parseable JSON line with an "error" field (never a raw
traceback on stdout) and exits 1: there is no replay of an older record.
A mode that measures the chip fails on any other platform.

Modes (BENCH_MODE):
  train (default) — jitted train-step throughput + analytic-FLOPs MFU.
  trainer         — END-TO-END Trainer.train() throughput: threaded
                    batcher + DevicePrefetcher + multi-step dispatch
                    (BENCH_SPD) + windowed metric fetches.  The gap to
                    `train` is the host-side overhead.
  decode          — batched on-device beam search: p50/p99 latency per
                    article + decoded tokens/sec.  (The reference pays
                    ~100 feed_dict round-trips per article, SURVEY §3.4.)
  attention       — A/B the fused Pallas additive-attention kernel vs the
                    XLA formula at reference scale and long-context scale.
  flash           — A/B the transformer's Pallas flash self-attention vs
                    the einsum formula (fwd+bwd) at T=BENCH_FLASH_T
                    (default 2048), head_dim 128.  TPU only.
  input           — host-side input-pipeline throughput: the threaded
                    bucketing Batcher packing synthetic reference-scale
                    articles into static-shape batches (no TPU; compare
                    against the device's train samples/s).
  serve           — concurrent serving (SERVING.md): BENCH_SERVE_REQS
                    requests from BENCH_SERVE_CONCURRENCY submitter
                    threads through ServingServer's admission queue;
                    p50/p99 END-TO-END latency (enqueue -> future
                    resolved, queue wait included), mean batch fill /
                    slot occupancy, and requests/sec.  `python bench.py
                    --serve` is shorthand for BENCH_MODE=serve;
                    `--serve-short-ratio=0.875` the bimodal mix's
                    short-request fraction (BENCH_SERVE_SHORT_RATIO;
                    fingerprinted only when non-default — ISSUE 11's
                    disaggregation axis);
                    `--serve-mode=continuous|microbatch` picks the
                    dispatch engine (BENCH_SERVE_MODE) and
                    `--serve-mix=bimodal` the seeded short/long article
                    mix (BENCH_SERVE_MIX) — the straggler workload the
                    continuous engine exists for;
                    `--serve-tier=beam|greedy|spec|draft`
                    (BENCH_SERVE_TIER, microbatch only) benches one
                    quality tier — spec rows carry measured acceptance
                    rate + the implied expected speedup (SERVING.md
                    "Quality tiers");
                    `--serve-replicas=N` (BENCH_SERVE_REPLICAS, with
                    `--serve-hedge-ms` / BENCH_SERVE_HEDGE_MS) routes
                    the load through the ISSUE-13 FleetRouter over N
                    in-process replicas — fleet rows carry hedge
                    spend/wins and requeue counts (SERVING.md "Elastic
                    fleet") and fingerprint their topology;
                    `--serve-zipf=S` (BENCH_SERVE_ZIPF) draws requests
                    zipf-distributed (p(k) ~ 1/(k+1)^S) over a pool of
                    distinct articles and arms the ISSUE-14 front door
                    (coalescing + the summary cache, capacity
                    BENCH_SERVE_CACHE) — the heavy-tailed trending-
                    article workload (SERVING.md "Front door");
                    fingerprint axis only when non-default;
                    `--serve-hier[=N]` (BENCH_SERVE_HIER, with
                    BENCH_HIER_CHUNKS / BENCH_HIER_APPEND) swaps in
                    the ISSUE-19 long-document map-reduce workload —
                    the row carries the fan-out makespan vs a
                    sequential per-chunk baseline plus the append
                    pass's cache_hit_rate (SERVING.md "Hierarchical
                    summarization"); fingerprint axis only when armed.
                    `--serve-arena-pages=N` (BENCH_SERVE_ARENA_PAGES)
                    runs the continuous engine over the ISSUE-20 paged
                    resident state — an N-page block-granular arena
                    (SERVING.md "Paged resident state"); fingerprint
                    axis only when armed.
                    Every serve row carries `cache_hit_rate`,
                    `coalesced_total`, `decodes_per_submit` (1.0
                    with the door dark — each submit decodes),
                    `arena_fill_mean`, and
                    `resident_bytes_per_slot_mean` (the provisioned
                    dense worst case on unarmed rows).
  bytes           — XLA cost-analysis byte accounting for the train
                    step (no execution; CPU-forced like input mode):
                    bytes accessed + intensity for the baseline config
                    and each byte-diet lever (--loss_chunk streaming
                    loss, bf16 optimizer state, both), with per-lever
                    reduction ratios.  Also emits decode rows (ISSUE 7,
                    PERF.md "Decode byte diet"): bytes per emitted
                    token + peak temp of the compiled beam search, per
                    loop kind and for one slot-kernel chunk
                    (BENCH_DECODE_CHUNK, default 25).  The
                    CPU-verifiable side of the PERF.md byte-diet claims.

Env overrides: BENCH_STEPS (20), BENCH_BATCH (16),
BENCH_PRESET=tiny|scaled (smoke scale / the long-input hidden-512
enc-800 shape), BENCH_FAMILY=transformer (bench the
second model family), BENCH_FLASH_T (flash-mode sequence length),
BENCH_SPD (trainer-mode steps_per_dispatch, 8), BENCH_UNROLL
(scan_unroll override), BENCH_LOSS_CHUNK (streaming-loss chunk; train/
trainer/bytes modes), BENCH_OPT_DTYPE (Adagrad accumulator storage
dtype), BENCH_TIMEOUT (600s per attempt),
BENCH_ATTEMPTS (2), BENCH_PLATFORM=cpu (force CPU child for smoke
runs).

Timing methodology: every timed window ends in `jax.block_until_ready`
on a value that data-depends on the timed computation (jax returns
before the device finishes).  The train / attention / flash measurement
loops run ON DEVICE (lax.scan / lax.fori_loop around the op, one
dispatch for the whole loop, iterations chained through a tiny
data-dependent carry so XLA cannot hoist the body).  decode keeps a
host-side per-iteration loop — its p50/p99 latency samples need
individual timings, so each sample includes one dispatch.
"""

from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys
import time

import numpy as np

# single-GPU K40m training anchor (See et al. setup: 230k iterations at
# batch 16 in "3 days 4 hours" = 13.5 samples/s — module docstring); the
# vs_baseline denominator everywhere
BASELINE_SAMPLES_PER_SEC = 13.5

_METRIC_BY_MODE = {
    "train": "train_samples_per_sec",
    "trainer": "trainer_e2e_samples_per_sec",
    "decode": "beam_decode_p50_latency_per_article",
    "attention": "attention_pallas_speedup_vs_xla",
    "flash": "flash_attention_speedup_vs_xla",
    "input": "input_pipeline_samples_per_sec",
    "serve": "serve_e2e_p50_latency_ms",
    "bytes": "train_step_bytes_accessed",
}


# --------------------------------------------------------------------------
# supervisor
# --------------------------------------------------------------------------

def _child_env() -> dict:
    from textsummarization_on_flink_tpu.utils import (
        set_default_compile_cache,
    )

    env = dict(os.environ)
    env["TS_BENCH_CHILD"] = "1"
    if _obs_snapshot_requested():
        # --obs-snapshot: the child embeds an obs registry dump in its
        # result row (argv is not forwarded to the re-exec'd child, so
        # the flag rides the environment)
        env["TS_OBS_SNAPSHOT"] = "1"
    set_default_compile_cache(env)
    if env.get("BENCH_MODE") in ("input", "bytes"):
        # host-only modes (bytes = XLA cost analysis, backend-portable by
        # design): they never need the chip, so they never take it
        env["BENCH_PLATFORM"] = "cpu"
    if env.get("BENCH_PLATFORM", "").lower() == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("JAX_PLATFORM_NAME", None)
    return env


def _env_flag(name: str) -> bool:
    """Boolean env knob: '1'/'on'/'true'/'yes' enable (so '=0' really
    disables — raw truthiness would read '0' as on)."""
    return os.environ.get(name, "").lower() in ("1", "on", "true", "yes")


def _bench_mesh() -> tuple:
    """BENCH_MESH="dpXtp" (e.g. "4x2") -> (dp, tp); (1, 1) when unset.
    Jax-free (the supervisor's fingerprint parses it); a malformed spec
    fails loudly here, at config time."""
    spec = os.environ.get("BENCH_MESH", "").strip().lower()
    if not spec:
        return (1, 1)
    try:
        dp, tp = (int(x) for x in spec.split("x"))
    except ValueError:
        raise ValueError(
            f"BENCH_MESH must be 'dpXtp' (e.g. '4x2'), got {spec!r}"
        ) from None
    if dp < 1 or tp < 1:
        raise ValueError(f"BENCH_MESH axes must be >= 1, got {spec!r}")
    return (dp, tp)


def _obs_snapshot_requested() -> bool:
    """`python bench.py --obs-snapshot` (or TS_OBS_SNAPSHOT=1): embed a
    compact obs registry dump in the result row so the BENCH trajectory
    carries telemetry (OBSERVABILITY.md)."""
    return "--obs-snapshot" in sys.argv[1:] or _env_flag("TS_OBS_SNAPSHOT")


def _obs_extra() -> dict:
    """The child-side snapshot payload ({} when not requested).  Compact:
    untouched metrics are dropped, so a train row carries the train-layer
    metrics only."""
    if not _obs_snapshot_requested():
        return {}
    from textsummarization_on_flink_tpu import obs

    return {"obs_snapshot": obs.snapshot(compact=True)}


_BIMODAL_POOL = 32  # articles in the generated bimodal mix (bench_serve)


def _bimodal_long_every(short_ratio: float) -> int:
    """The bimodal mix's long-article cadence for a requested short
    fraction: every long_every-th request is long."""
    return max(2, round(1.0 / (1.0 - short_ratio)))


def _effective_short_ratio(short_ratio: float) -> float:
    """The short fraction the generated _BIMODAL_POOL-article mix
    ACTUALLY has: the cadence quantizes the request (0.6 -> every 2nd
    long -> a 0.5 mix) AND the finite pool quantizes the cadence
    (longs sit at indices 0, le, 2le, ... < pool, so 0.8 -> le=5 -> 7
    longs of 32 -> 0.7812).  Both the published row and the
    fingerprint must carry the workload that ran, not the one that was
    asked for — otherwise two asks that generate the identical article
    list (e.g. any cadence > pool places exactly one long) would carry
    different fingerprints and one measured mix could stand in for
    another."""
    n_long = -(-_BIMODAL_POOL // _bimodal_long_every(short_ratio))
    return round(1.0 - n_long / _BIMODAL_POOL, 4)


def _config_fingerprint() -> dict:
    """The config axes that distinguish one row from another, as seen
    from the environment.  Successful records embed this, so a reader
    can tell e.g. a batch-64 record from the default batch-16 ask."""
    mode = os.environ.get("BENCH_MODE", "train")
    fp = {"mode": mode}
    # a CPU smoke record must never stand in for a TPU ask (or vice
    # versa); input/bytes modes are host-only by construction
    if mode in ("input", "bytes"):
        fp["platform"] = "cpu"
    else:
        fp["platform"] = (os.environ.get("BENCH_PLATFORM", "").lower()
                          or "tpu")
    mesh = _bench_mesh()
    if mesh != (1, 1):
        # sharded-mesh axis (ISSUE 8): a dp x tp measurement is a
        # different compiled program (registry-driven collectives) and
        # must never stand in for a single-device ask.  Added only when
        # non-default so pre-existing banked records (no such key) keep
        # matching default asks.
        fp["mesh"] = f"{mesh[0]}x{mesh[1]}"
    if mode in ("train", "trainer"):
        # byte-diet lever axes (ISSUE 5): each is a DIFFERENT compiled
        # program, so rows must never cross-substitute.  Added only when
        # non-default so pre-existing banked records (no such keys) keep
        # matching default asks.
        chunk = int(os.environ.get("BENCH_LOSS_CHUNK", "0"))
        if chunk:
            fp["loss_chunk"] = chunk
        opt_dtype = os.environ.get("BENCH_OPT_DTYPE", "") or "float32"
        if opt_dtype != "float32":
            fp["opt_dtype"] = opt_dtype
    if mode == "bytes":
        # the bytes child sweeps the opt-dtype lever internally (and
        # BENCH_LOSS_CHUNK only picks the swept chunk size, carried as
        # "chunk"), so neither train-mode lever axis applies here — a
        # duplicate axis would split identical records across
        # fingerprints and defeat incremental banking
        fp["batch"] = int(os.environ.get("BENCH_BATCH", "16"))
        fp["preset"] = os.environ.get("BENCH_PRESET", "ref") or "ref"
        fp["family"] = (os.environ.get("BENCH_FAMILY", "")
                        or "pointer_generator")
        fp["chunk"] = int(os.environ.get("BENCH_LOSS_CHUNK", "25"))
        # remat/unroll reach the compiled programs via _preset_overrides
        # (e.g. an exported BENCH_REMAT=1 from a sweep): different
        # programs, different record — same rule as train mode
        fp["remat"] = _env_flag("BENCH_REMAT")
        if os.environ.get("BENCH_UNROLL"):
            fp["unroll"] = int(os.environ["BENCH_UNROLL"])
        # the decode rows' slot/chunked programs change with the chunk
        # length; non-default only, so banked records keep matching
        if int(os.environ.get("BENCH_DECODE_CHUNK", "25")) != 25:
            fp["decode_chunk"] = int(os.environ["BENCH_DECODE_CHUNK"])
    if mode in ("train", "trainer", "decode"):
        fp["batch"] = int(os.environ.get(
            "BENCH_BATCH", "4" if mode == "decode" else "16"))
        fp["preset"] = os.environ.get("BENCH_PRESET", "ref") or "ref"
        fp["family"] = (os.environ.get("BENCH_FAMILY", "")
                        or "pointer_generator")
        if mode in ("train", "trainer"):
            # remat trades recompute for bytes — a different program; a
            # remat measurement must never stand in for a non-remat ask
            fp["remat"] = _env_flag("BENCH_REMAT")
        # record the RESOLVED kernel choice, not the raw env string:
        # "auto"'s meaning changed once (pallas-on-tpu -> xla), and a
        # fingerprint of intent would cross-substitute semantically
        # different measurements across that change
        pallas_env = (os.environ.get("TS_PALLAS", "") or "auto").lower()
        fp["pallas"] = "on" if pallas_env in ("1", "on", "true") else "off"
        # transformer flash self-attention routing: record the RESOLVED
        # kernel choice (same rule as pallas above — an intent
        # fingerprint would cross-substitute across any future change
        # to auto's threshold).  The pg family never reads TS_FLASH, so
        # it always resolves 'off'; auto resolves on the ask's encoder
        # shape via _use_flash's frozen rule (aligned T>=1024).
        if fp["family"] != "transformer":
            fp["flash"] = "off"
        else:
            from textsummarization_on_flink_tpu.config import (
                HParams,
                flash_mode_from_env,
            )

            resolved = flash_mode_from_env()
            if resolved == "auto":
                hp = HParams(batch_size=fp["batch"],
                             **_preset_overrides())
                hd = hp.hidden_dim // hp.num_heads
                aligned = hp.max_enc_steps % 128 == 0 and hd % 128 == 0
                resolved = ("on" if aligned and hp.max_enc_steps >= 1024
                            else "off")
            fp["flash"] = resolved
        if os.environ.get("BENCH_UNROLL"):
            fp["unroll"] = int(os.environ["BENCH_UNROLL"])
        else:  # the HParams default (config.py is dependency-light)
            from textsummarization_on_flink_tpu.config import HParams

            fp["unroll"] = HParams.scan_unroll
    if mode == "trainer":
        fp["spd"] = int(os.environ.get("BENCH_SPD", "8"))
    if mode == "serve":
        fp["batch"] = int(os.environ.get("BENCH_BATCH", "4"))
        fp["preset"] = os.environ.get("BENCH_PRESET", "ref") or "ref"
        fp["family"] = (os.environ.get("BENCH_FAMILY", "")
                        or "pointer_generator")
        # the coalescing window trades latency for fill: rows measured
        # under different windows must never cross-substitute
        fp["wait_ms"] = float(os.environ.get("BENCH_SERVE_WAIT_MS", "20"))
        fp["reqs"] = int(os.environ.get("BENCH_SERVE_REQS", "64"))
        fp["concurrency"] = int(
            os.environ.get("BENCH_SERVE_CONCURRENCY", "8"))
        # quality-tier axis (ISSUE 10): each tier runs a DIFFERENT
        # compiled decode program (beam vs beam-1 vs spec vs draft) —
        # rows must never cross-substitute.  Added only when
        # non-default so pre-existing banked records keep matching.
        if os.environ.get("BENCH_SERVE_TIER", "beam") != "beam":
            fp["tier"] = os.environ["BENCH_SERVE_TIER"]
            # distilled-narrow-draft axes (ISSUE 12): a narrow draft
            # (different width + factored head = different compiled
            # programs) and an adaptive controller (host-stepped cycle
            # loop vs one dispatch) must never cross-substitute —
            # added only when non-default, per house convention, so
            # banked equal-width spec records keep matching.  The
            # EFFECTIVE rank rides along whenever a factored head is in
            # play (explicit BENCH_DRAFT_RANK, or the width-derived
            # default — same resolution bench_serve applies), so two
            # ranks can never share a fingerprint.  Guarded to the
            # tiers that BUILD a draft (spec/draft): greedy/legacy runs
            # ignore BENCH_DRAFT_*, and a stray env var must not split
            # identical workloads across fingerprints (the PR-11
            # short_ratio rule).
            if os.environ["BENCH_SERVE_TIER"] in ("spec", "draft"):
                dh = int(os.environ.get("BENCH_DRAFT_HIDDEN", "0"))
                dr = int(os.environ.get("BENCH_DRAFT_RANK", str(dh // 2)))
                if dh:
                    fp["draft_hidden"] = dh
                if dr:
                    fp["draft_rank"] = dr
                if os.environ.get("BENCH_SPEC_ADAPTIVE", "").lower() in \
                        ("1", "on", "true", "yes"):
                    fp["spec_k_adaptive"] = True
        # bimodal short-request fraction (ISSUE 11): a different mix is
        # a different workload — a 7/8-short measurement must never
        # stand in for the default 3/4-short ask.  Recorded as the
        # EFFECTIVE (cadence- and pool-quantized) fraction the mix
        # actually has, only on the bimodal mix (the ratio has no
        # effect on other workloads — a stray env var must not split
        # identical uniform-mix records across fingerprints), and only
        # when non-default so pre-existing bimodal records keep
        # matching.
        if os.environ.get("BENCH_SERVE_MIX", "buckets") == "bimodal":
            sr = _effective_short_ratio(
                float(os.environ.get("BENCH_SERVE_SHORT_RATIO", "0.75")))
            if sr != 0.75:
                fp["short_ratio"] = sr
        # front-door axis (ISSUE 14): a zipf mix with the door armed
        # does fundamentally less work than a uniform mix (coalesced
        # followers and cache hits never decode) — zipf rows must never
        # stand in for non-zipf rows.  Non-default only, house
        # convention; the cache capacity rides along because a smaller
        # cache means more re-decodes under the same S.
        if float(os.environ.get("BENCH_SERVE_ZIPF", "0") or 0) > 0:
            fp["zipf"] = float(os.environ["BENCH_SERVE_ZIPF"])
            fp["cache"] = int(os.environ.get("BENCH_SERVE_CACHE", "256"))
        # elastic-fleet axis (ISSUE 13): N routed replicas run a
        # DIFFERENT serving topology than one server (router hop,
        # hedging, per-replica queues) — fleet rows must never stand in
        # for single-server rows.  Non-default only, per house
        # convention, so banked records keep matching; the hedge budget
        # rides along whenever it is armed (hedged and unhedged fleets
        # do different work).
        if os.environ.get("BENCH_SERVE_REPLICAS", "1") not in ("", "1"):
            fp["replicas"] = int(os.environ["BENCH_SERVE_REPLICAS"])
            if float(os.environ.get("BENCH_SERVE_HEDGE_MS", "0") or 0):
                fp["hedge_ms"] = float(os.environ["BENCH_SERVE_HEDGE_MS"])
        # hierarchical long-document axis (ISSUE 19): the map-reduce
        # fan-out is a DIFFERENT workload than the request-stream
        # benches (one parent per document, chunk-tier decodes + one
        # reduce, an append pass that mostly cache-hits) — hier rows
        # must never stand in for plain serve rows.  Non-default only,
        # house convention; the fan-out width rides along because
        # makespans scale with it.
        if os.environ.get("BENCH_SERVE_HIER", "").lower() in \
                ("1", "on", "true", "yes"):
            fp["hier_chunks"] = int(os.environ.get("BENCH_HIER_CHUNKS",
                                                   "6"))
        # paged-arena axis (ISSUE 20): an armed arena runs the PAGED
        # slot kernels (page-table gathers, pooled encoder leaves) and
        # admission is gated by free pages — a different memory story
        # AND a different admission policy than dense residents, so
        # arena rows must never stand in for dense rows.  Non-default
        # only, house convention, so banked dense records keep matching;
        # the page count IS the axis (capacity changes backpressure).
        if int(os.environ.get("BENCH_SERVE_ARENA_PAGES", "0") or 0) > 0:
            fp["arena"] = int(os.environ["BENCH_SERVE_ARENA_PAGES"])
    if mode == "decode":
        # while vs scan vs chunked are different compiled programs —
        # never cross-substitute their latencies (nor chunk sizes: C=1
        # is per-step dynamic cost, C=T degenerates to scan).  Record
        # the RESOLVED kind, not "auto" (same rule as the pallas/flash
        # axes), through the resolver the child's search routes on
        from textsummarization_on_flink_tpu.config import (
            resolve_beam_loop,
        )

        loop = resolve_beam_loop()
        fp["beam_loop"] = loop
        # decode params source: a trained fixture
        # and a STOP-biased init produce different generated-step counts,
        # so their latencies must never cross-substitute — and neither
        # may stand in for the old random-init worst case
        fp["params"] = _decode_params_spec(fp["family"])
        if loop == "chunked":
            # same env resolution beam_search.resolved_chunk uses; lives
            # in config.py because this supervisor stays off jax
            from textsummarization_on_flink_tpu.config import (
                beam_chunk_from_env,
            )

            fp["chunk"] = beam_chunk_from_env()
    elif mode == "flash":
        fp["flash_t"] = int(os.environ.get("BENCH_FLASH_T", "2048"))
    elif mode == "input":
        fp["batch"] = int(os.environ.get("BENCH_BATCH", "16"))
    return fp


_digest_cache: dict = {}


def _file_digest(path: str) -> str:
    """Short content digest of a fixture file, cached on
    (size, mtime_ns) so repeated fingerprints don't re-hash tens of MB.
    Nanosecond mtime: a same-second, same-size fixture regen must
    invalidate the cache, not serve the previous content's digest."""
    import hashlib

    st = os.stat(path)
    key = (path, st.st_size, st.st_mtime_ns)
    if key not in _digest_cache:
        h = hashlib.sha256()
        with open(path, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
        _digest_cache.clear()  # one fixture per process in practice
        _digest_cache[key] = h.hexdigest()[:12]
    return _digest_cache[key]


def _decode_fixture_path(family: str) -> str:
    """Trained decode fixture for BENCH_MODE=decode (generated by
    exp/train_decode_fixture.py; deliberately untracked — the script is
    the committed recipe).  BENCH_DECODE_FIXTURE overrides the path, or
    disables the fixture entirely with ''/'0'/'none'."""
    repo_root = os.path.dirname(os.path.abspath(__file__))
    return os.environ.get(
        "BENCH_DECODE_FIXTURE",
        os.path.join(repo_root, "exp", f"decode_fixture_{family}.npz"))


def _decode_params_spec(family: str) -> str:
    """How BENCH_MODE=decode obtains STOP-capable params (random init
    never emits STOP, so every beam would run all max_dec_steps and the
    loop A/B could only measure overhead).
    'fixture' when the trained fixture file exists, else
    'stop_bias:<b>' — init params with BENCH_STOP_BIAS (default 6.0,
    calibrated on CPU at reference scale: pg finishes at the
    min_dec_steps floor of 36 generated steps, transformer spreads
    36-100 with p50 45) added to the STOP logit of every vocab-sized
    bias vector.  Dependency-light: callable from the supervisor's
    fingerprint."""
    path = _decode_fixture_path(family)
    # default-path auto-detection only applies at the reference preset:
    # the fixture is trained at reference scale, so a tiny/scaled-preset
    # run must not pick it up (shape-guard failure on every smoke run).
    # An EXPLICIT BENCH_DECODE_FIXTURE is honored as asked — a mismatch
    # fails loudly in _load_decode_fixture.
    explicit = os.environ.get("BENCH_DECODE_FIXTURE") is not None
    preset_ok = (explicit
                 or (os.environ.get("BENCH_PRESET", "ref") or "ref") == "ref")
    if preset_ok and path and path.lower() not in ("0", "none"):
        if os.path.exists(path):
            # the spec carries the fixture's content identity: a
            # REGENERATED fixture (different --steps/--seed => different
            # gen-step distribution and latency) must invalidate banked
            # decode rows, not cross-substitute them
            return f"fixture:{_file_digest(path)}"
        if explicit:
            # an explicitly requested fixture must never silently degrade
            # to stop-bias params — the banked rows would masquerade as
            # trained-fixture numbers
            raise ValueError(
                f"BENCH_DECODE_FIXTURE={path} does not exist "
                f"(generate it: exp/train_decode_fixture.py, or set "
                f"BENCH_DECODE_FIXTURE=none for STOP-biased init params)")
    return "stop_bias:%g" % float(os.environ.get("BENCH_STOP_BIAS", "6.0"))


def supervise() -> None:
    mode = os.environ.get("BENCH_MODE", "train")
    metric = _METRIC_BY_MODE.get(mode, f"bench_{mode}")
    attempts = int(os.environ.get("BENCH_ATTEMPTS", "2"))
    # the full-scale beam search takes a long first compile; give
    # non-train modes more headroom by default
    default_timeout = "600" if mode == "train" else "1200"
    timeout = float(os.environ.get("BENCH_TIMEOUT", default_timeout))
    repo_root = os.path.dirname(os.path.abspath(__file__))
    last_err = "no attempts made"
    for attempt in range(1, attempts + 1):
        try:
            proc = subprocess.run(
                [sys.executable, "-u", os.path.abspath(__file__)],
                env=_child_env(), cwd=repo_root, timeout=timeout,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        except subprocess.TimeoutExpired as e:
            out = e.output or ""
            if isinstance(out, bytes):
                out = out.decode("utf-8", "replace")
            last_err = (f"attempt {attempt}/{attempts} timed out after "
                        f"{timeout:.0f}s")
            sys.stderr.write(f"[bench] {last_err}\n{out[-1500:]}\n")
            continue
        # the child's LAST parseable JSON line with "metric" is the result
        result = None
        for line in (proc.stdout or "").splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                if isinstance(obj, dict) and "metric" in obj:
                    result = obj
        if result is not None and "error" not in result:
            result.setdefault(
                "captured_at",
                datetime.datetime.now(datetime.timezone.utc)
                .strftime("%Y-%m-%dT%H:%M:%SZ"))
            result.setdefault("config_fingerprint", _config_fingerprint())
            if os.environ.get("BENCH_RUN_TAG"):
                result.setdefault("run", os.environ["BENCH_RUN_TAG"])
            print(json.dumps(result))
            return
        last_err = (f"attempt {attempt}/{attempts}: child rc="
                    f"{proc.returncode}, "
                    + (result.get("error", "no JSON result line")
                       if result else "no JSON result line"))
        sys.stderr.write(f"[bench] {last_err}\n"
                         f"{(proc.stdout or '')[-1500:]}\n")
        if result is not None and result.get("retryable") is False:
            break  # deterministic failure (bad mode, wrong platform,
            # kernel mismatch): a retry would only repeat it
    print(json.dumps({"metric": metric, "value": 0.0, "unit": "n/a",
                      "vs_baseline": 0.0, "error": last_err}))
    sys.exit(1)


# --------------------------------------------------------------------------
# analytic FLOPs model (for MFU)
# --------------------------------------------------------------------------

def transformer_flops_per_step(hps) -> float:
    """Analytic training FLOPs/step for the transformer family: per-layer
    attention projections + score/value matmuls + FFN, plus the tied
    [H, V] output projection; training = 3x forward."""
    B, Te, Td = hps.batch_size, hps.max_enc_steps, hps.max_dec_steps
    H, V = hps.hidden_dim, hps.vocab_size
    F = hps.ffn_width
    enc_layer = 4 * Te * H * H + 2 * Te * Te * H + 2 * Te * H * F
    dec_layer = (4 * Td * H * H + 2 * Td * Td * H       # causal self-attn
                 + 2 * Td * H * H + 2 * Te * H * H      # cross q,o + k,v
                 + 2 * Td * Te * H                      # cross scores+ctx
                 + 2 * Td * H * F)                      # ffn
    macs = B * (hps.enc_layers * enc_layer + hps.dec_layers * dec_layer
                + Td * H * V)
    return float(3 * 2 * macs)


def train_flops_per_step(hps) -> float:
    """Analytic training FLOPs/step for the pointer-generator.

    MAC counts per sample, forward pass (model shapes per
    /root/reference/src/main/python/pointer-generator/model.py:76-238,
    attention_decoder.py:58-174); training = 3x forward (backward ~= 2x).
    The H x vocab output projection dominates at reference scale.
    """
    B, Te, Td = hps.batch_size, hps.max_enc_steps, hps.max_dec_steps
    H, E, V = hps.hidden_dim, hps.emb_dim, hps.vocab_size
    D = 2 * H  # biLSTM state width == attention feature width
    enc_lstm = 2 * Te * (E + H) * 4 * H       # two directions
    reduce_states = 2 * D * H                 # c and h bi->uni reductions
    enc_feats = Te * D * D                    # W_h h_i, hoisted per sequence
    dec_per_step = (
        (E + D) * E          # input+context merge linear
        + (E + H) * 4 * H    # decoder LSTM cell
        + D * D              # W_s state projection ([c,h] -> D)
        + Te * D             # v . tanh(feats) energy reduction
        + Te * D             # context = attn @ enc_states
        + (2 * D + E)        # p_gen linear
        + (H + D) * H        # output merge ([cell_out, ctx] -> H)
        + H * V              # output projection (dominant)
    )
    macs = B * (enc_lstm + reduce_states + enc_feats + Td * dec_per_step)
    return float(3 * 2 * macs)  # 2 FLOPs/MAC; fwd+bwd ~= 3x fwd


_PEAK_BF16_TFLOPS = {
    # per-chip bf16 peaks (public TPU specs), keyed by device_kind
    "v2": 45.0, "v3": 123.0, "v4": 275.0,
    "v5 lite": 197.0, "v5e": 197.0, "v5p": 459.0,
    "v6 lite": 918.0, "v6e": 918.0,
}


def peak_flops_for(device) -> float:
    """Per-chip bf16 peak FLOP/s of `device`.  A device that is not in
    the table is an error, not a default: an MFU against a guessed peak
    is not a measurement."""
    kind = (getattr(device, "device_kind", "") or "").lower()
    for key in sorted(_PEAK_BF16_TFLOPS, key=len, reverse=True):
        if key in kind:
            return _PEAK_BF16_TFLOPS[key] * 1e12
    raise ValueError(
        f"no bf16 peak for device_kind {kind!r}: add it to "
        f"_PEAK_BF16_TFLOPS with its source")


def _mfu_fields(dev, platform: str, flops: float, step_time: float) -> dict:
    """MFU against the device's bf16 peak.  A CPU smoke row (platform
    forced by BENCH_PLATFORM=cpu) has no chip to be utilised and carries
    no MFU; any other device that is not in the table raises."""
    if platform == "cpu":
        return {}
    peak = peak_flops_for(dev)
    return {"mfu": round(flops / step_time / peak, 4),
            "peak_tflops": peak / 1e12}


def _device_info():
    import jax

    devices = jax.devices()
    dev = devices[0]
    return dev, {"platform": jax.default_backend(),
                 "device": getattr(dev, "device_kind", str(dev)),
                 "device_count": len(devices)}


def _require_tpu(metric: str) -> None:
    """A mode that measures a TPU kernel fails on any other platform (a
    deterministic failure: the supervisor does not retry it)."""
    import jax

    if jax.default_backend() != "tpu":
        print(json.dumps({"metric": metric, "value": 0.0, "unit": "x",
                          "vs_baseline": 0.0, "retryable": False,
                          "error": f"{metric} requires a TPU backend "
                                   f"(have {jax.default_backend()!r})"}))
        sys.exit(1)


# --------------------------------------------------------------------------
# children
# --------------------------------------------------------------------------

def _preset_overrides() -> dict:
    """BENCH_PRESET=tiny shrinks the model for smoke runs (full-scale
    beam-search compiles take minutes on CPU); =scaled is the
    long-input shape (hidden 512, enc 800);
    default is the reference scale.  BENCH_FAMILY=transformer benches
    the second model family (BART-class; 6+6 layers at hidden_dim
    width)."""
    out = {}
    if os.environ.get("BENCH_PRESET") == "tiny":
        out.update(hidden_dim=16, emb_dim=8, vocab_size=200,
                   max_enc_steps=32, max_dec_steps=8, beam_size=2,
                   min_dec_steps=1, max_oov_buckets=8)
    elif os.environ.get("BENCH_PRESET") == "scaled":
        out.update(hidden_dim=512, max_enc_steps=800)
    if os.environ.get("BENCH_UNROLL"):
        out["scan_unroll"] = int(os.environ["BENCH_UNROLL"])
    if os.environ.get("BENCH_LOSS_CHUNK"):
        # streaming chunked vocab loss (ISSUE 5 byte diet): the
        # [T_dec, B, V] scores tensor never materializes
        out["loss_chunk"] = int(os.environ["BENCH_LOSS_CHUNK"])
    if os.environ.get("BENCH_OPT_DTYPE"):
        # bf16 Adagrad accumulator storage (half the optimizer-state HBM)
        out["opt_state_dtype"] = os.environ["BENCH_OPT_DTYPE"]
    if _env_flag("BENCH_REMAT"):
        # roofline-motivated A/B: on a bandwidth-bound step
        # recomputing the [T_dec, B, V] scores block in backward may SAVE
        # time, not just memory
        out["remat"] = True
    mesh = _bench_mesh()
    if mesh != (1, 1):
        # (dp, tp) mesh axes for the unified sharded step (ISSUE 8):
        # the registry-driven layouts are different compiled programs,
        # fingerprinted via the `mesh` axis below
        out["dp"], out["tp"] = mesh
    family = os.environ.get("BENCH_FAMILY", "")
    if family:
        out["model_family"] = family
        if family == "transformer" \
                and os.environ.get("BENCH_PRESET") == "tiny":
            out["num_heads"] = 4  # tiny preset: 16/4 heads
            out["enc_layers"] = out["dec_layers"] = 2
    return out


def bench_train() -> None:
    import functools

    import jax

    from textsummarization_on_flink_tpu.config import HParams
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib
    from __graft_entry__ import _example_arrays

    steps = int(os.environ.get("BENCH_STEPS", "20"))
    batch = int(os.environ.get("BENCH_BATCH", "16"))

    hps = HParams(batch_size=batch, compute_dtype="bfloat16",
                  **_preset_overrides())

    state = trainer_lib.init_train_state(hps, hps.vocab_size, seed=0)
    step_fn = trainer_lib.make_train_step(hps)
    arrays = _example_arrays(hps, np.random.RandomState(0))
    arrays = jax.device_put(arrays)

    def k_steps(state, arrays, k):
        def body(s, _):
            s, m = step_fn(s, arrays)
            return s, m.loss
        state, losses = jax.lax.scan(body, state, None, length=k)
        return state, losses[-1]

    run = jax.jit(functools.partial(k_steps, k=steps), donate_argnums=0)
    state, loss0 = run(state, arrays)   # compile + warm (steps real steps)
    jax.block_until_ready(loss0)

    t0 = time.perf_counter()
    state, loss_last = run(state, arrays)
    loss = float(jax.block_until_ready(loss_last))
    dt = max(time.perf_counter() - t0, 1e-9)

    if not np.isfinite(loss):
        print(json.dumps({"metric": "train_samples_per_sec", "value": 0.0,
                          "unit": "samples/s", "vs_baseline": 0.0,
                          "error": f"non-finite loss {loss}"}))
        sys.exit(1)

    # the un-sharded jit runs on exactly one chip, so the measured
    # throughput IS the per-chip number
    samples_per_sec = steps * batch / dt
    step_time = dt / steps
    baseline = BASELINE_SAMPLES_PER_SEC
    dev, info = _device_info()
    flops = (transformer_flops_per_step(hps)
             if hps.model_family == "transformer"
             else train_flops_per_step(hps))
    rec = {
        "metric": "train_samples_per_sec",
        "value": round(samples_per_sec, 2),
        "unit": "samples/s",
        "vs_baseline": round(samples_per_sec / baseline, 2),
        "step_time_ms": round(step_time * 1e3, 3),
        "flops_per_step": flops,
        "loss": round(loss, 4),
        "model_family": hps.model_family,
        "timing": f"on-device lax.scan of {steps} steps, "
                  f"block_until_ready on the last loss",
    }
    rec.update(_mfu_fields(dev, info["platform"], flops, step_time))
    rec.update(info)
    rec.update(_obs_extra())
    print(json.dumps(rec))


def _stop_biased(params, vsize: int, bias: float):
    """STOP-capable params from a random init: add `bias` to the STOP
    logit of every vocab-sized bias vector (pg output_projection.v,
    transformer out_bias).  Random-init logits are effectively
    stationary per article, so an article either emits STOP as soon as
    min_dec_steps allows or never — the calibrated default (see
    _decode_params_spec) puts finishes in the realistic band instead of
    the all-100-steps worst case."""
    import jax

    from textsummarization_on_flink_tpu.data.vocab import STOP_ID

    def bump(x):
        if getattr(x, "shape", None) == (vsize,):
            return x.at[STOP_ID].add(bias)
        return x

    return jax.tree_util.tree_map(bump, params)


def _load_decode_fixture(path: str, init):
    """Load a trained decode fixture (npz of keystr->array, written by
    exp/train_decode_fixture.py) into init_params' tree structure,
    validated leaf-for-leaf so a stale or wrong-scale fixture fails
    loudly instead of silently measuring a different model."""
    import jax

    data = np.load(path)
    flat, treedef = jax.tree_util.tree_flatten_with_path(init)
    extra = set(data.files) - {jax.tree_util.keystr(k) for k, _ in flat}
    if extra:
        raise ValueError(
            f"decode fixture {path} has keys the model does not: "
            f"{sorted(extra)[:4]} — trained under a different config "
            f"(e.g. coverage)? regenerate: exp/train_decode_fixture.py")
    leaves = []
    for key_path, leaf in flat:
        key = jax.tree_util.keystr(key_path)
        if key not in data:
            raise ValueError(f"decode fixture {path} is missing {key!r} "
                             f"(regenerate: exp/train_decode_fixture.py)")
        arr = np.asarray(data[key])
        if arr.shape != leaf.shape:
            raise ValueError(
                f"decode fixture {path} leaf {key!r} has shape {arr.shape}, "
                f"model expects {leaf.shape} (wrong scale? regenerate)")
        leaves.append(arr.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def bench_decode() -> None:
    """BENCH_MODE=decode: batched beam-search decode at the reference
    serving config (batch 4, enc 400, dec 100, beam 4,
    TensorFlowTest.java:40-53).  One device dispatch per batch of
    articles vs the reference's ~100 feed_dict round trips per article."""
    import jax

    from textsummarization_on_flink_tpu.config import HParams
    from textsummarization_on_flink_tpu.decode import beam_search
    from textsummarization_on_flink_tpu.models import get_family
    from __graft_entry__ import _example_arrays

    iters = int(os.environ.get("BENCH_STEPS", "10"))
    batch = int(os.environ.get("BENCH_BATCH", "4"))
    hps = HParams(batch_size=batch, mode="decode", coverage=True,
                  **_preset_overrides())
    # coverage mirrors the reference decode config for the pg family
    # (TensorFlowTest.java:40-53); the transformer decode path never
    # reads it
    if hps.model_family == "transformer":
        hps = hps.replace(coverage=False)
    family = get_family(hps.model_family)
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(0))
    params_spec = _decode_params_spec(hps.model_family)
    if params_spec.startswith("fixture"):
        params = _load_decode_fixture(
            _decode_fixture_path(hps.model_family), params)
    else:
        params = _stop_biased(params, hps.vocab_size,
                              float(params_spec.split(":", 1)[1]))
    arrays = _example_arrays(hps, np.random.RandomState(0))
    arrays = {k: v for k, v in arrays.items()
              if not k.startswith(("dec_", "target_"))}
    arrays = jax.device_put(arrays)

    beam_loop = beam_search._loop_kind()  # TS_BEAM_LOOP env override
    chunk = beam_search.resolved_chunk(beam_loop)  # part of the cache key
    out = beam_search.run_beam_search_jit(params, hps, arrays,
                                          loop=beam_loop,
                                          chunk=chunk)  # compile
    np.asarray(jax.device_get(out.length))
    lat_raw = []
    tokens = 0
    t_total = 0.0
    all_lengths = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = beam_search.run_beam_search_jit(params, hps, arrays,
                                              loop=beam_loop, chunk=chunk)
        # fetching the lengths (data-dependent on the whole decode loop)
        # is the fence
        lengths = np.asarray(jax.device_get(out.length))
        dt = time.perf_counter() - t0
        lat_raw.append(dt / batch)
        t_total += dt
        # length includes START (beam_search.py:57-58); generated = len-1
        tokens += int(np.sum(lengths - 1))
        all_lengths.extend(int(x) for x in lengths)

    def pct(xs, q):
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(len(xs) * q))]

    _, info = _device_info()
    rec = {
        "metric": "beam_decode_p50_latency_per_article",
        "value": round(pct(lat_raw, 0.5) * 1000, 2),
        "unit": "ms",
        "vs_baseline": 0.0,  # the reference publishes no decode latency
        "p99_ms": round(pct(lat_raw, 0.99) * 1000, 2),
        "tokens_per_sec": round(tokens / t_total, 1),
        "beam_size": hps.beam_size,
        "batch": batch,
        "beam_loop": beam_loop,
        "params_source": params_spec,
        # generated steps of each best hypothesis (length-1): the proxy
        # for how much of max_dec_steps early-exit loops (while/chunked)
        # can save vs scan's fixed iteration count — the data the
        # TS_BEAM_LOOP auto-choice decision needs (PERF.md decode rows)
        "gen_steps_p50": int(np.median(all_lengths)) - 1,
        "gen_steps_max": max(all_lengths) - 1,
        "max_dec_steps": hps.max_dec_steps,
    }
    rec.update(info)
    rec.update(_obs_extra())
    print(json.dumps(rec))


def bench_attention() -> None:
    """BENCH_MODE=attention: A/B the fused Pallas kernel (simple + blocked
    long-context variants, ops/pallas_attention.py) against the XLA
    formula — same-output check plus a timing ratio.  TPU only: the
    kernels are compiled, never interpreted."""
    import jax
    import jax.numpy as jnp

    from textsummarization_on_flink_tpu.ops import pallas_attention as pa

    iters = int(os.environ.get("BENCH_STEPS", "50"))
    _require_tpu("attention_pallas_speedup_vs_xla")
    rng = np.random.RandomState(0)

    def make_args(B, T, D):
        es = rng.randn(B, T, D).astype(np.float32)
        ef = rng.randn(B, T, D).astype(np.float32)
        lens = rng.randint(T // 2, T + 1, size=(B,))
        mask = (np.arange(T)[None, :] < lens[:, None]).astype(np.float32)
        df = rng.randn(B, D).astype(np.float32)
        cov = np.abs(rng.randn(B, T)).astype(np.float32)
        v = rng.randn(D).astype(np.float32)
        wc = rng.randn(D).astype(np.float32)
        return tuple(jax.device_put(x) for x in (es, ef, mask, df, cov, v, wc))

    def timed(fn, args):
        """iters calls chained ON DEVICE: one fori_loop dispatch, each
        iteration's dec_feats perturbed by a tiny carry computed from the
        previous context so XLA cannot hoist the loop body."""
        es, ef, mask, df, cov, v, wc = args

        @jax.jit
        def run_many():
            def body(i, carry):
                ctx, _ = fn(es, ef, mask, df + carry, cov, v, wc)
                return ctx[:1, :1] * 1e-30
            return jax.lax.fori_loop(0, iters, body,
                                     jnp.zeros((1, 1), jnp.float32))

        jax.block_until_ready(run_many())  # compile + warm
        t0 = time.perf_counter()
        jax.block_until_ready(run_many())
        return max(time.perf_counter() - t0, 1e-9) / iters

    results = {}
    speedups = []
    # reference scale (B16 T400 D512) f32 + bf16 encoder streams (the
    # compute_dtype=bfloat16 train path hands the op bf16 es/ef), and
    # long-context (T4096 -> blocked kernel)
    scales = {"ref": (16, 400, 512, False),
              "ref_bf16": (16, 400, 512, True),
              "longctx": (4, 4096, 512, False)}
    for name, (B, T, D, bf16_stream) in scales.items():
        args = make_args(B, T, D)
        if bf16_stream:
            args = (args[0].astype(jnp.bfloat16),
                    args[1].astype(jnp.bfloat16)) + args[2:]
        xla = jax.jit(lambda *a: pa._attention_xla(*a, True))
        if T * D > pa._SIMPLE_KERNEL_MAX_ELEMS:
            kern = jax.jit(lambda *a: pa._attention_pallas_blocked(
                *a, True))
        else:
            kern = jax.jit(lambda *a: pa._attention_pallas(*a, True))
        # correctness BEFORE the timing loops (a mismatch is deterministic
        # — fail fast and tell the supervisor not to retry).  The XLA
        # formula's context matmul runs at the backend's default
        # precision, the kernel's at HIGHEST: the ctx gate allows for it
        out_xla = jax.block_until_ready(xla(*args))
        out_pal = jax.block_until_ready(kern(*args))
        ctx_err = float(jnp.max(jnp.abs(out_xla[0] - out_pal[0])))
        attn_err = float(jnp.max(jnp.abs(out_xla[1] - out_pal[1])))
        if ctx_err > 2e-2 or attn_err > 1e-3:
            print(json.dumps({
                "metric": "attention_pallas_speedup_vs_xla", "value": 0.0,
                "unit": "x", "vs_baseline": 0.0, "retryable": False,
                "error": f"pallas/xla mismatch at {name}: "
                         f"ctx {ctx_err} attn {attn_err}"}))
            sys.exit(1)
        t_xla = timed(xla, args)
        t_pal = timed(kern, args)
        results[name] = {
            "xla_us": round(t_xla * 1e6, 1),
            "pallas_us": round(t_pal * 1e6, 1),
            "speedup": round(t_xla / t_pal, 3),
            "max_ctx_err": ctx_err,
            "max_attn_err": attn_err,
        }
        speedups.append(t_xla / t_pal)
    _, info = _device_info()
    rec = {
        "metric": "attention_pallas_speedup_vs_xla",
        "value": round(speedups[0], 3),  # reference scale is the headline
        "unit": "x",
        "vs_baseline": round(speedups[0], 3),
        "scales": results,
        "timing": f"on-device fori_loop of {iters} iters, carry-chained",
    }
    rec.update(info)
    print(json.dumps(rec))


def bench_flash() -> None:
    """BENCH_MODE=flash: A/B the transformer's Pallas flash self-attention
    against the einsum formula at a long-context, lane-aligned scale
    (T=2048, hd=128) — same-output gate, then a fwd+bwd timing ratio."""
    import jax
    import jax.numpy as jnp

    from textsummarization_on_flink_tpu.config import HParams
    from textsummarization_on_flink_tpu.models import transformer as tfm

    iters = int(os.environ.get("BENCH_STEPS", "30"))
    # TS_FLASH=on off-TPU raises in _use_flash; say so in the row's terms
    _require_tpu("flash_attention_speedup_vs_xla")
    B, T = 4, int(os.environ.get("BENCH_FLASH_T", "2048"))
    hps = HParams(model_family="transformer", hidden_dim=1024, num_heads=8,
                  max_enc_steps=T, batch_size=B)
    rng = np.random.RandomState(0)
    p = {k: jnp.asarray(rng.randn(1024, 1024) * 0.02, jnp.float32)
         for k in ("wq", "wk", "wv", "wo")}
    x = jnp.asarray(rng.randn(B, T, 1024) * 0.1, jnp.float32)
    lens = rng.randint(T // 2, T + 1, size=(B,))
    mask = jnp.asarray((np.arange(T)[None] < lens[:, None]), jnp.float32)

    def f(x):
        out = tfm._self_attention(hps, p, x, mask, causal=False)
        # mask the LOSS: padding-query rows legitimately differ between
        # the two paths and must not leak gradient into the real rows
        # being compared
        return jnp.sum((out * mask[:, :, None]) ** 2)

    def run(flag):
        os.environ["TS_FLASH"] = flag
        # compile NOW, while the env flag is set — jit traces lazily and
        # _use_flash reads TS_FLASH at trace time
        return jax.jit(lambda x: jax.grad(f)(x)).lower(x).compile()

    f_xla, f_flash = run("off"), run("on")
    g0 = jax.block_until_ready(f_xla(x))
    g1 = jax.block_until_ready(f_flash(x))
    # gate correctness on REAL rows only (flash leaves padding-query rows
    # undefined by design; downstream masks discard them)
    real = np.asarray(mask)[:, :, None] > 0
    err = float(jnp.max(jnp.abs(jnp.where(real, g0 - g1, 0.0))))
    scale = float(jnp.max(jnp.abs(jnp.where(real, g0, 0.0))))
    if err > 1e-2 * max(scale, 1.0):
        print(json.dumps({"metric": "flash_attention_speedup_vs_xla",
                          "value": 0.0, "unit": "x", "vs_baseline": 0.0,
                          "retryable": False,
                          "error": f"flash/xla grad mismatch {err} "
                                   f"(scale {scale})"}))
        sys.exit(1)

    def timed(flag):
        """iters fwd+bwd passes of the same `f` chained on device; the
        input is perturbed by a carry from the previous gradient so XLA
        cannot hoist the body.  Traced+compiled while TS_FLASH is set
        (read at trace time)."""
        os.environ["TS_FLASH"] = flag

        @jax.jit
        def run_many(x):
            def body(i, carry):
                g = jax.grad(f)(x + carry)
                return g[:1, :1, :1] * 1e-30
            return jax.lax.fori_loop(0, iters, body,
                                     jnp.zeros((1, 1, 1), jnp.float32))

        jax.block_until_ready(run_many(x))  # compile + warm, flag set
        t0 = time.perf_counter()
        jax.block_until_ready(run_many(x))
        return max(time.perf_counter() - t0, 1e-9) / iters

    t_xla, t_flash = timed("off"), timed("on")
    _, info = _device_info()
    rec = {
        "metric": "flash_attention_speedup_vs_xla",
        "value": round(t_xla / t_flash, 3),
        "unit": "x",
        "vs_baseline": round(t_xla / t_flash, 3),
        "xla_ms": round(t_xla * 1e3, 3),
        "flash_ms": round(t_flash * 1e3, 3),
        "T": T, "head_dim": 128, "max_grad_err": err,
    }
    rec.update(info)
    print(json.dumps(rec))


def _synthetic_dataset(tmp: str, hps, n_examples: int = 512):
    """Write a synthetic chunked CNN/DM-scale dataset under tmp and
    return its (glob_pattern, vocab).  The vocab is sized to
    hps.vocab_size (words + 4 specials) so model shapes — above all the
    FLOP-dominant [H, vocab] projection — match the non-synthetic
    benches; article text samples a 2k-word subset (ids must recur for
    the bucketing/OOV machinery to do real work)."""
    from textsummarization_on_flink_tpu.data import TFExample, Vocab
    from textsummarization_on_flink_tpu.data.chunks import write_chunked

    rng = np.random.RandomState(0)
    n_words = max(hps.vocab_size - 4, 100)  # 4 specials complete the size
    words = [f"w{i}" for i in range(n_words)]
    vocab = Vocab(words=words)
    words = words[:2000]  # text draws from a recurring subset
    exs = []
    for _ in range(n_examples):
        art_len = rng.randint(hps.max_enc_steps // 2,
                              hps.max_enc_steps + 100)
        art = " ".join(rng.choice(words, size=art_len))
        abs_len = rng.randint(hps.max_dec_steps // 2, hps.max_dec_steps)
        abstract = "<s> " + " ".join(rng.choice(words, size=abs_len)) \
            + " . </s>"
        exs.append(TFExample()
                   .set_bytes("article", art.encode())
                   .set_bytes("abstract", abstract.encode()))
    write_chunked(os.path.join(tmp, "train"), exs, chunk_size=128)
    return os.path.join(tmp, "train_*.bin"), vocab


def bench_input() -> None:
    """BENCH_MODE=input: host-side input-pipeline throughput — the
    threaded bucketing Batcher (16+4 producer threads, reference
    batcher.py:252-253 parity) packing a synthetic chunked CNN/DM-scale
    dataset into static-shape train batches.  No TPU involved; the
    number to compare against is the device's train samples/s (the
    pipeline must exceed it to keep the chip busy)."""
    import shutil
    import tempfile

    from textsummarization_on_flink_tpu.config import HParams
    from textsummarization_on_flink_tpu.data.batcher import Batcher

    batch = int(os.environ.get("BENCH_BATCH", "16"))
    hps = HParams(batch_size=batch, **_preset_overrides())

    tmp = tempfile.mkdtemp(prefix="bench_input_")
    try:
        pattern, vocab = _synthetic_dataset(tmp, hps)
        b = Batcher(pattern, vocab, hps, single_pass=False)
        b.next_batch()  # wait for the producer threads to come up
        # the batch queue holds up to 100 pre-built batches; timing a
        # drain of that backlog would measure Queue.get, not pipeline
        # throughput.  Pull until the queue is momentarily empty so the
        # clock starts from ~zero backlog, then count batches produced
        # during a fixed window (consumed ≈ produced from an empty
        # start — any end-of-window backlog is uncounted, so the number
        # errs low, never high).
        drained = 0
        while b.queued_batches() > 0 and drained < 300:
            b.next_batch()
            drained += 1
        seconds = float(os.environ.get("BENCH_SECONDS", "3"))
        t0 = time.perf_counter()
        n_batches = 0
        while time.perf_counter() - t0 < seconds:
            b.next_batch()
            n_batches += 1
        dt = time.perf_counter() - t0
        rate = n_batches * batch / dt
        rec = {
            "metric": "input_pipeline_samples_per_sec",
            "value": round(rate, 1),
            "unit": "samples/s",
            "vs_baseline": round(rate / BASELINE_SAMPLES_PER_SEC, 2),
            "batch": batch,
            "batches_timed": n_batches,
            "note": "host-only; must exceed device train samples/s",
        }
        rec.update(_obs_extra())
        print(json.dumps(rec))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_serve_hier() -> None:
    """--serve-hier: the ISSUE-19 long-document workload — ONE
    multi-chunk document map-reduced through HierarchicalSummarizer
    over a live server, against a sequential per-chunk baseline, plus
    an APPEND re-summarize whose cache-hit rate is the row's dedup
    evidence.  The headline is the fan-out makespan (parent submit ->
    HierResult, reduce included); `sequential_ms` is the same chunk
    set decoded one-at-a-time on the same warm server (distinct
    articles, so the front door cannot help it)."""
    import shutil
    import tempfile

    import jax

    from textsummarization_on_flink_tpu import obs
    from textsummarization_on_flink_tpu.config import HParams
    from textsummarization_on_flink_tpu.data.vocab import Vocab
    from textsummarization_on_flink_tpu.decode.decoder import (
        BeamSearchDecoder,
    )
    from textsummarization_on_flink_tpu.models import get_family
    from textsummarization_on_flink_tpu.serve.hiersum import (
        DocumentSession,
        HierarchicalSummarizer,
    )
    from textsummarization_on_flink_tpu.serve.server import ServingServer

    chunks_n = int(os.environ.get("BENCH_HIER_CHUNKS", "6"))
    append_n = int(os.environ.get("BENCH_HIER_APPEND", "2"))
    if chunks_n < 2 or append_n < 1:
        raise ValueError(
            f"BENCH_HIER_CHUNKS must be >= 2 and BENCH_HIER_APPEND >= 1, "
            f"got {chunks_n}/{append_n}")
    serve_mode = os.environ.get("BENCH_SERVE_MODE", "microbatch")
    wait_ms = float(os.environ.get("BENCH_SERVE_WAIT_MS", "20"))
    hps = HParams(batch_size=int(os.environ.get("BENCH_BATCH", "4")),
                  mode="decode", coverage=True,
                  serve_max_wait_ms=wait_ms, serve_mode=serve_mode,
                  serve_max_queue=max(256, 2 * chunks_n),
                  serve_coalesce=True, serve_cache_entries=256,
                  **_preset_overrides())
    hps.validate()
    if hps.model_family == "transformer":
        hps = hps.replace(coverage=False)
    # full-width chunks (hier_chunk_words=0 -> max_enc_steps): every
    # chunk runs the same encoder shape, so sequential-vs-fan-out is a
    # scheduling comparison, not a padding artifact
    cw = hps.max_enc_steps
    n_words = max(hps.vocab_size - 4, 100)
    vocab = Vocab(words=[f"w{i}" for i in range(n_words)])
    pool = [f"w{i}" for i in range(min(n_words, 2000))]

    def words(start: int, count: int) -> str:
        # deterministic distinct-ish streams: doc A, doc B (the
        # sequential baseline), and the appended tail never share a
        # chunk, so the cache only ever helps the APPEND pass
        return " ".join(pool[(start + i) % len(pool)]
                        for i in range(count))

    doc = words(0, chunks_n * cw)
    seq_chunks = [words(7 + (chunks_n + i) * cw, cw)
                  for i in range(chunks_n)]
    tail = words(3 + 2 * chunks_n * cw, append_n * cw)
    family = get_family(hps.model_family)
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(0))
    params = _stop_biased(params, hps.vocab_size,
                          float(os.environ.get("BENCH_STOP_BIAS", "6.0")))
    tmp = tempfile.mkdtemp(prefix="bench_serve_hier_")
    try:
        decoder = BeamSearchDecoder(hps, vocab, batcher=None,
                                    params=params, decode_root=tmp)
        server = ServingServer(hps, vocab, decoder=decoder)
        reg = obs.registry()
        hs = HierarchicalSummarizer(server, hps)
        with server:
            # compile both tiers the workload uses (chunk tier +
            # reduce tier) before any timed phase
            server.submit(words(11, cw), uuid="warm-g",
                          tier="" if serve_mode == "continuous"
                          else "greedy").result(timeout=1200)
            server.submit(words(13, cw), uuid="warm-b").result(timeout=1200)

            t0 = time.perf_counter()
            for i, chunk in enumerate(seq_chunks):
                server.submit(chunk, uuid=f"seq{i}", block=True,
                              tier="" if serve_mode == "continuous"
                              else "greedy").result(timeout=1200)
            sequential_s = time.perf_counter() - t0

            sess = DocumentSession("bench-doc", doc)
            t0 = time.perf_counter()
            hs.summarize("", session=sess, block=True).result(timeout=1200)
            fanout_s = time.perf_counter() - t0

            hits0 = reg.counter("serve/hier_chunk_cache_hits_total").value
            done0 = reg.counter("serve/completed_total").value
            sess.append(tail)
            t0 = time.perf_counter()
            hs.summarize("", session=sess, block=True).result(timeout=1200)
            append_s = time.perf_counter() - t0
            hits = reg.counter(
                "serve/hier_chunk_cache_hits_total").value - hits0
            append_decodes = reg.counter(
                "serve/completed_total").value - done0
        fid = reg.histogram("serve/hier_copy_fidelity")
        rec = {
            "metric": "serve_hier_fanout_makespan_ms",
            "value": round(fanout_s * 1000, 2),
            "unit": "ms",
            "vs_baseline": 0.0,  # the reference publishes no serving numbers
            "serve_mode": serve_mode,
            "hier_chunks": chunks_n,
            "chunk_words": cw,
            "sequential_ms": round(sequential_s * 1000, 2),
            # < 1.0 == the fan-out beat decoding the chunks one at a
            # time (the committed virtual-time ceiling lives in
            # SERVE_SLO.json "hierarchical"; this is the wall-clock
            # evidence at bench scale)
            "makespan_ratio": round(fanout_s / sequential_s, 4)
            if sequential_s else 0.0,
            "append_ms": round(append_s * 1000, 2),
            "append_chunks": append_n,
            # dedup by construction: pre-append chunks / resubmitted
            # chunks served from the front-door cache on the append pass
            "append_cache_hit_rate": round(
                hits / (chunks_n + append_n), 4),
            "append_decodes": int(append_decodes),
            "copy_fidelity_mean": round(fid.mean, 4),
            "wait_ms": wait_ms,
            "model_family": hps.model_family,
            "timing": "wall-clock makespan, parent submit -> HierResult "
                      "(reduce included); sequential = same-width chunks "
                      "decoded one at a time on the same warm server",
        }
        print(json.dumps(rec))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_serve() -> None:
    """BENCH_MODE=serve: concurrent serving end-to-end — submitter
    threads push requests through the ServingServer's admission queue
    and dynamic micro-batcher (SERVING.md) against a STOP-capable
    tiny-or-reference model; the headline is the p50 END-TO-END latency
    a caller observes (enqueue -> resolved future, queue wait and
    coalescing window included), alongside p99, mean batch fill, and
    aggregate requests/sec.  `--serve-hier` (BENCH_SERVE_HIER=1)
    swaps in the ISSUE-19 long-document map-reduce workload instead
    (bench_serve_hier)."""
    if os.environ.get("BENCH_SERVE_HIER", "").lower() in \
            ("1", "on", "true", "yes"):
        bench_serve_hier()
        return
    import shutil
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from textsummarization_on_flink_tpu import obs
    from textsummarization_on_flink_tpu.config import (
        HParams,
        resolve_refill_chunk,
        resolve_serve_slots,
    )
    from textsummarization_on_flink_tpu.data.vocab import Vocab
    from textsummarization_on_flink_tpu.decode.decoder import (
        BeamSearchDecoder,
    )
    from textsummarization_on_flink_tpu.models import get_family
    from textsummarization_on_flink_tpu.serve.batcher import resolve_buckets
    from textsummarization_on_flink_tpu.serve.server import ServingServer

    from textsummarization_on_flink_tpu.config import SERVE_TIERS

    reqs = int(os.environ.get("BENCH_SERVE_REQS", "64"))
    conc = int(os.environ.get("BENCH_SERVE_CONCURRENCY", "8"))
    batch = int(os.environ.get("BENCH_BATCH", "4"))
    wait_ms = float(os.environ.get("BENCH_SERVE_WAIT_MS", "20"))
    serve_mode = os.environ.get("BENCH_SERVE_MODE", "microbatch")
    mix = os.environ.get("BENCH_SERVE_MIX", "buckets")
    if mix not in ("buckets", "bimodal"):
        # serve_mode is validated by hps.validate(); the mix needs its
        # own guard or a typo silently runs the wrong workload under
        # the requested label
        raise ValueError(
            f"BENCH_SERVE_MIX must be 'buckets' or 'bimodal', got {mix!r}")
    tier = os.environ.get("BENCH_SERVE_TIER", "beam")
    if tier not in SERVE_TIERS:
        raise ValueError(
            f"BENCH_SERVE_TIER must be one of {SERVE_TIERS}, got {tier!r}")
    if serve_mode == "continuous" and tier != "beam":
        raise ValueError(
            "continuous serving decodes at the beam tier only; drop "
            "BENCH_SERVE_TIER or use BENCH_SERVE_MODE=microbatch")
    slots = int(os.environ.get("BENCH_SERVE_SLOTS", "0"))
    refill_chunk = int(os.environ.get("BENCH_SERVE_CHUNK", "0"))
    replicas_n = int(os.environ.get("BENCH_SERVE_REPLICAS", "1"))
    hedge_ms = float(os.environ.get("BENCH_SERVE_HEDGE_MS", "0"))
    # the ISSUE-14 front door: a zipf exponent > 0 draws the request
    # stream heavy-tailed over a pool of DISTINCT articles and arms
    # coalescing + the summary cache (capacity BENCH_SERVE_CACHE) —
    # the duplicate-heavy trending-article workload
    zipf_s = float(os.environ.get("BENCH_SERVE_ZIPF", "0") or 0)
    if zipf_s < 0:
        raise ValueError(
            f"BENCH_SERVE_ZIPF must be >= 0 (0 = off), got {zipf_s}")
    cache_entries = int(os.environ.get("BENCH_SERVE_CACHE", "256")) \
        if zipf_s > 0 else 0
    # paged resident state (ISSUE 20): BENCH_SERVE_ARENA_PAGES=N arms
    # the block-granular page arena — continuous mode decodes through
    # the paged slot kernels and admission waits on free pages
    arena_pages = int(os.environ.get("BENCH_SERVE_ARENA_PAGES", "0") or 0)
    if arena_pages < 0:
        raise ValueError(
            f"BENCH_SERVE_ARENA_PAGES must be >= 0 (0 = dense), got "
            f"{arena_pages}")
    if arena_pages and serve_mode != "continuous":
        raise ValueError(
            "the page arena serves the continuous engine's residents; "
            "drop BENCH_SERVE_ARENA_PAGES or use "
            "BENCH_SERVE_MODE=continuous")
    hps = HParams(batch_size=batch, mode="decode", coverage=True,
                  serve_max_wait_ms=wait_ms, serve_mode=serve_mode,
                  serve_slots=slots, serve_refill_chunk=refill_chunk,
                  serve_max_queue=max(256, reqs),
                  serve_replicas=replicas_n, serve_hedge_ms=hedge_ms,
                  serve_coalesce=zipf_s > 0,
                  serve_cache_entries=cache_entries,
                  serve_arena_pages=arena_pages,
                  **_preset_overrides())
    if tier in ("spec", "draft"):
        # the draft model source: the mapped bootstrap for the
        # transformer family (the real serving recipe), fresh init for
        # the others (exactness holds either way; acceptance is the
        # row's evidence, not an assumption).  BENCH_DRAFT_HIDDEN /
        # BENCH_DRAFT_RANK / BENCH_SPEC_ADAPTIVE bench the ISSUE-12
        # narrow draft + adaptive controller (fingerprinted above when
        # non-default).
        draft_hidden = int(os.environ.get("BENCH_DRAFT_HIDDEN", "0"))
        draft_rank = int(os.environ.get(
            "BENCH_DRAFT_RANK", str(draft_hidden // 2)))
        adaptive = os.environ.get("BENCH_SPEC_ADAPTIVE", "").lower() in \
            ("1", "on", "true", "yes")
        hps = hps.replace(
            spec_draft="map" if hps.model_family == "transformer"
            else "fresh",
            draft_hidden=draft_hidden, draft_vocab_rank=draft_rank,
            spec_k_adaptive=adaptive)
    hps.validate()
    if hps.model_family == "transformer":
        hps = hps.replace(coverage=False)
    rng = np.random.RandomState(0)
    n_words = max(hps.vocab_size - 4, 100)
    vocab = Vocab(words=[f"w{i}" for i in range(n_words)])
    pool = [f"w{i}" for i in range(min(n_words, 2000))]
    buckets = resolve_buckets(hps)
    # short-request fraction of the bimodal mix (ISSUE 11): default
    # 0.75 = the historical every-4th-long shape; fingerprinted only
    # when non-default so banked bimodal records keep matching.  The
    # row carries the EFFECTIVE (cadence-quantized) fraction — see
    # _effective_short_ratio.
    asked_ratio = float(os.environ.get("BENCH_SERVE_SHORT_RATIO",
                                       "0.75"))
    if not 0.0 < asked_ratio < 1.0:
        raise ValueError(
            f"BENCH_SERVE_SHORT_RATIO must be in (0, 1), got "
            f"{asked_ratio}")
    short_ratio = _effective_short_ratio(asked_ratio)
    articles = []
    if mix == "bimodal":
        # the straggler workload (SERVE_SLO.json shape): every
        # long_every-th request a max-length article, the rest short —
        # the load where the micro-batch dispatch barrier hurts, slot
        # refill wins, and (ISSUE 11) disaggregation stops the shorts
        # from paying the longs' encoder shapes
        long_every = _bimodal_long_every(asked_ratio)
        short_n = max(4, hps.max_enc_steps // 8)
        for i in range(_BIMODAL_POOL):
            n = hps.max_enc_steps if i % long_every == 0 else \
                rng.randint(max(short_n // 2, 1), short_n + 1)
            articles.append(" ".join(rng.choice(pool, size=n)))
        rng.shuffle(articles)
    else:
        # one article per bucket length plus a mixed request stream, so
        # the warm phase compiles EVERY bucket and the timed phase
        # exercises bucket routing instead of a single shape
        for i in range(32):
            limit = buckets[i % len(buckets)]
            n = rng.randint(max(limit // 2, 1), limit + 1)
            articles.append(" ".join(rng.choice(pool, size=n)))
    # zipf request ORDER over whichever article pool the mix built:
    # p(k) ~ 1/(k+1)^S, seeded — the same heavy-tailed draw as the
    # SERVE_SLO.json front_door gate, at bench scale
    zipf_order = None
    if zipf_s > 0:
        weights = np.array([1.0 / (k + 1) ** zipf_s
                            for k in range(len(articles))])
        zipf_order = rng.choice(len(articles), size=reqs,
                                p=weights / weights.sum())
    family = get_family(hps.model_family)
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(0))
    params = _stop_biased(params, hps.vocab_size,
                          float(os.environ.get("BENCH_STOP_BIAS", "6.0")))
    tmp = tempfile.mkdtemp(prefix="bench_serve_")
    try:
        decoder = BeamSearchDecoder(hps, vocab, batcher=None, params=params,
                                    decode_root=tmp)
        if replicas_n > 1:
            # the elastic fleet (ISSUE 13; --serve-replicas): N
            # in-process replicas behind the REAL FleetRouter, sharing
            # the process registry (counters/histograms aggregate
            # across replicas; the per-replica gauges last-writer-win —
            # routing reads each replica's live stats() surface, not
            # the gauges) and the ONE decoder (shared jit cache: the
            # fleet row benches routing + dispatch concurrency, not N
            # redundant compiles)
            from textsummarization_on_flink_tpu.serve.fleet import (
                FleetRouter,
            )

            server = FleetRouter(
                [ServingServer(hps, vocab, decoder=decoder)
                 for _ in range(replicas_n)], hps)
        else:
            server = ServingServer(hps, vocab, decoder=decoder)
        reg = obs.registry()
        fill_h = reg.histogram("serve/batch_fill")
        occ_h = reg.histogram("serve/slot_occupancy")
        with server:
            if serve_mode == "continuous":
                # the decode kernels warm on the first request (ONE
                # resident shape: init/pack/step/unpack), but prefill
                # compiles once per BUCKET (ISSUE 11) — warm every
                # bucket with an exactly-b-word article so no prefill
                # compile lands in the timed run.  Submitted together:
                # the slot engine decodes the warmers concurrently, so
                # warmup costs ~one decode, not len(buckets) decodes
                warm_futs = [
                    server.submit(
                        " ".join(pool[i % len(pool)] for i in range(b)),
                        uuid=f"warm{b}")
                    for b in buckets]
                for f in warm_futs:
                    f.result(timeout=1200)
            else:
                for b in buckets:  # compile every bucket before timing
                    # exactly b words -> enc_len == b -> bucket_for
                    # picks bucket b itself (a shorter article would
                    # warm a SMALLER bucket and leave b's compile in
                    # the timed run)
                    words = [pool[i % len(pool)] for i in range(b)]
                    server.submit(" ".join(words), uuid=f"warm{b}",
                                  tier=tier).result(timeout=1200)
            fills0 = (fill_h.count, fill_h.sum)
            occ0 = (occ_h.count, occ_h.sum)
            # counters snapshot AFTER warm-up, like the histograms: the
            # published row must carry the TIMED run only, on one
            # measurement basis
            refills0 = reg.counter("serve/slot_refills_total").value
            prefill0 = reg.counter("serve/prefill_total").value
            # profiler phase snapshot (obs/profile.py, ISSUE 16): the
            # timed window's per-phase means ride the row as evidence
            # fields — fingerprint-neutral, like the trace split below
            from textsummarization_on_flink_tpu.obs import (
                profile as profile_lib,
            )

            phases0 = profile_lib.profiler_for(reg).phase_stats()
            evict0 = reg.counter("serve/deadline_evictions_total").value
            shed0 = reg.counter("serve/shed_total").value
            degraded0 = reg.counter("serve/degraded_total").value
            drafted0 = reg.counter("decode/spec_draft_tokens_total").value
            accepted0 = reg.counter(
                "decode/spec_accepted_tokens_total").value
            cycles0 = reg.counter("decode/spec_cycles_total").value
            # front-door accounting (ISSUE 14): completed counts only
            # requests that actually DECODED (cache hits resolve at
            # submit, followers from their leader), so decodes/submit
            # is the redundant-work ratio the zipf row exists to show
            completed0 = reg.counter("serve/completed_total").value
            hits0 = reg.counter("serve/cache_hits_total").value
            misses0 = reg.counter("serve/cache_misses_total").value
            coalesced0 = reg.counter("serve/coalesced_total").value
            # arena evidence snapshots (ISSUE 20): the fill histogram
            # gets one observation per refill tick, so the timed
            # delta's mean is the run's mean arena occupancy
            arena_h = reg.histogram("serve/arena_fill")
            arena_fill0 = (arena_h.count, arena_h.sum)
            arena_fail0 = reg.counter(
                "serve/arena_alloc_failures_total").value
            lat: list = []
            # trace-derived per-request breakdown (ISSUE 9 satellite):
            # TEE the timed phase's lifecycle events into memory (an
            # installed EventSink keeps receiving everything — the
            # capture must not eat the run's events.jsonl) and split
            # every e2e latency into queue wait vs resident/decode
            # time — row fields only, fingerprint-neutral
            from textsummarization_on_flink_tpu.obs.export import MemorySink

            prev_sink, trace_sink = reg.event_sink, MemorySink()

            class _Tee:
                def emit(self, rec):
                    ok = trace_sink.emit(rec)
                    if prev_sink is not None:
                        ok = prev_sink.emit(rec) and ok
                    return ok

            def one(i: int) -> None:
                art = articles[int(zipf_order[i])] if zipf_order \
                    is not None else articles[i % len(articles)]
                t0 = time.perf_counter()
                server.submit(art, uuid=f"r{i}",
                              block=True, tier=tier).result(timeout=1200)
                lat.append(time.perf_counter() - t0)

            reg.event_sink = _Tee()
            try:
                t0 = time.perf_counter()
                with ThreadPoolExecutor(max_workers=conc) as ex:
                    list(ex.map(one, range(reqs)))
                wall = time.perf_counter() - t0
            finally:
                reg.event_sink = prev_sink
        # continuous mode dispatches chunks, not micro-batches: report
        # the batch stats as zero rather than clamping to a fabricated
        # one-batch row
        n_batches = fill_h.count - fills0[0]
        fill_mean = ((fill_h.sum - fills0[1]) / n_batches) if n_batches \
            else 0.0
        n_chunks = occ_h.count - occ0[0]
        if serve_mode == "continuous":
            # mean fraction of slots doing useful work per chunk step
            occupancy = ((occ_h.sum - occ0[1]) / n_chunks) if n_chunks \
                else 0.0
        else:
            # micro-batch analogue: mean dispatch fill over the device
            # batch shape (hides straggler waste — the honest
            # per-step utilization comparison lives in SERVE_SLO.json)
            occupancy = fill_mean / hps.batch_size

        def pct(xs, q):
            xs = sorted(xs)
            return xs[min(len(xs) - 1, int(len(xs) * q))]

        # profiler-derived phase means over the timed window: the
        # continuous path's serve/prefill + serve/dispatch (one sample
        # per decode chunk), the micro-batch path's per-tier
        # serve/dispatch (prefill stays 0 there — no prefill stage)
        phases1 = profile_lib.profiler_for(reg).phase_stats()

        def phase_ms_mean(name: str) -> float:
            c1, s1, _ = phases1.get(name, (0, 0.0, 0.0))
            c0, s0, _ = phases0.get(name, (0, 0.0, 0.0))
            n = c1 - c0
            return round(1e3 * (s1 - s0) / n, 3) if n else 0.0

        # arena occupancy over the timed window + the resident-bytes
        # accounting it implies (ISSUE 20).  decode_resident_bytes is
        # eval_shape only (no compile) at the ENGINE's slot count; the
        # paged mean prices the fixed per-slot share plus the measured
        # mean pages in use per slot — the same accounting the
        # BYTE_BUDGET decode.resident gate commits, fed with this run's
        # observed fill instead of an assumed mix.
        arena_ticks = arena_h.count - arena_fill0[0]
        arena_fill_mean = round(
            (arena_h.sum - arena_fill0[1]) / arena_ticks, 4) \
            if arena_ticks else 0.0
        from __graft_entry__ import decode_resident_bytes

        slots_n = resolve_serve_slots(hps)
        rb = decode_resident_bytes(hps.replace(batch_size=slots_n),
                                   pages=arena_pages or None)
        resident_mean = int(
            rb["fixed_bytes_per_slot"]
            + arena_fill_mean * rb["arena_pages"] * rb["page_bytes"]
            / slots_n)

        # per-uuid first-occurrence timestamps of each lifecycle stage
        per_req: dict = {}
        for ev in trace_sink.records():
            if ev.get("kind") != "request":
                continue
            stages = per_req.setdefault(ev.get("uuid", ""), {})
            stages.setdefault(ev.get("event"), ev.get("ts_us", 0))
        queue_ms, resident_ms = [], []
        for uuid, st in per_req.items():
            if not uuid.startswith("r"):
                continue  # timed requests only (warm-up is w/"warm*")
            if "enqueue" in st and "admit" in st:
                queue_ms.append((st["admit"] - st["enqueue"]) / 1e3)
            end = st.get("finish", st.get("resolve"))
            if "admit" in st and end is not None:
                resident_ms.append((end - st["admit"]) / 1e3)

        _, info = _device_info()
        rec = {
            "metric": "serve_e2e_p50_latency_ms",
            "value": round(pct(lat, 0.5) * 1000, 2),
            "unit": "ms",
            "vs_baseline": 0.0,  # the reference publishes no serving numbers
            "p99_ms": round(pct(lat, 0.99) * 1000, 2),
            "serve_mode": serve_mode,
            "tier": tier,
            "mix": mix,
            "short_ratio": short_ratio if mix == "bimodal" else None,
            "batch_fill_mean": round(fill_mean, 2),
            "occupancy_mean": round(occupancy, 3),
            "batches": n_batches,
            "chunks": n_chunks,
            "slot_refills_total": int(
                reg.counter("serve/slot_refills_total").value - refills0),
            # the disaggregation evidence (ISSUE 11): timed requests
            # through the bucketed prefill stage (0 in microbatch mode)
            "prefill_total": int(
                reg.counter("serve/prefill_total").value - prefill0),
            "deadline_evictions_total": int(
                reg.counter("serve/deadline_evictions_total").value
                - evict0),
            "requests_per_sec": round(reqs / wall, 2),
            # the trace-derived split of the e2e latency above: where a
            # request's time went (queue wait vs resident/decode) —
            # mean + p99 over the timed requests, from the same
            # lifecycle events scripts/trace_summary.py --request reads
            "queue_ms_mean": round(sum(queue_ms) / len(queue_ms), 2)
            if queue_ms else 0.0,
            "queue_ms_p99": round(pct(queue_ms, 0.99), 2)
            if queue_ms else 0.0,
            "resident_ms_mean": round(sum(resident_ms) / len(resident_ms),
                                      2) if resident_ms else 0.0,
            "resident_ms_p99": round(pct(resident_ms, 0.99), 2)
            if resident_ms else 0.0,
            # profiler phase means (ISSUE 16; evidence only): encoder
            # prefill per request vs decode wall per dispatch/chunk
            "prefill_ms_mean": phase_ms_mean("serve/prefill"),
            "decode_ms_mean": phase_ms_mean("serve/dispatch"),
            "traced_requests": len(queue_ms),
            "reqs": reqs,
            "concurrency": conc,
            "batch": batch,
            # through the config resolvers, so the published record
            # carries the slot count / chunk the engine ACTUALLY ran
            # (serve_slots=0 / serve_refill_chunk=0 are sentinels)
            "slots": resolve_serve_slots(hps),
            "refill_chunk": resolve_refill_chunk(hps),
            "wait_ms": wait_ms,
            "buckets": buckets,
            "shed_total": int(reg.counter("serve/shed_total").value - shed0),
            "degraded_total": int(
                reg.counter("serve/degraded_total").value - degraded0),
            # front-door row fields (ISSUE 14): present on every serve
            # row — a dark door reads hit_rate 0, coalesced 0,
            # decodes_per_submit 1.0 (every submit decoded)
            "cache_hit_rate": round(
                (reg.counter("serve/cache_hits_total").value - hits0)
                / max(1.0, (reg.counter("serve/cache_hits_total").value
                            - hits0)
                      + (reg.counter("serve/cache_misses_total").value
                         - misses0)), 4),
            "coalesced_total": int(
                reg.counter("serve/coalesced_total").value - coalesced0),
            "decodes_per_submit": round(
                (reg.counter("serve/completed_total").value - completed0)
                / reqs, 4),
            # paged-arena evidence (ISSUE 20; every serve row, like
            # cache_hit_rate): mean arena occupancy over the timed
            # window (one fill observation per refill tick; 0.0 on
            # dense rows — the histogram never fires) and the MEAN
            # resident bytes one slot actually held — dense rows report
            # the provisioned worst case, arena rows price the fixed
            # share plus the measured mean pages in use.  Fields ride
            # the row; only BENCH_SERVE_ARENA_PAGES is a fingerprint
            # axis.
            "arena_fill_mean": arena_fill_mean,
            "resident_bytes_per_slot_mean": resident_mean,
            "arena_alloc_failures_total": int(
                reg.counter("serve/arena_alloc_failures_total").value
                - arena_fail0),
            # telemetry-plane evidence (ISSUE 15): per-tier fast-window
            # burn rates off the installed SLO engine (SLO_POLICY.json
            # tier_latency objective; {} when no engine installed) and
            # the number of latency buckets carrying a trace exemplar —
            # a row with exemplars is a row whose p99 names a concrete
            # request.  Row fields only, fingerprint-neutral.
            "slo_burn_fast_by_tier": {
                row["key"]: row["burn_fast"]
                for row in (reg.slo.evaluate() if reg.slo is not None
                            else ())
                if row["objective"] == "tier_latency"},
            "exemplar_count": sum(
                len(m.exemplars())
                for m in (reg.get("serve/e2e_latency_seconds"),)
                if m is not None),
            "model_family": hps.model_family,
            "spec_k": int(hps.spec_k),
            "timing": "wall-clock per request, enqueue -> resolved future "
                      "(queue wait + coalescing window included)",
        }
        if replicas_n > 1:
            # fleet evidence (ISSUE 13): hedge spend/wins and requeues
            # ride the row so a fleet measurement carries its own
            # redundant-work accounting (FastSeq's lesson, priced)
            rec["replicas"] = replicas_n
            rec["hedge_ms"] = hedge_ms
            rec["hedges_total"] = int(
                reg.counter("serve/hedges_total").value)
            rec["hedge_wins_total"] = int(
                reg.counter("serve/hedge_wins_total").value)
            rec["requeued_total"] = int(
                reg.counter("serve/requeued_total").value)
        if tier == "spec":
            # measured acceptance -> expected speedup (the BYTE_BUDGET
            # "spec" evidence trail): acceptance comes from THIS run's
            # verifier; the draft/full cost ratio is the committed
            # ceiling, so the published number is conservative
            from textsummarization_on_flink_tpu.decode.speculative import (
                expected_speedup,
            )

            drafted = int(reg.counter(
                "decode/spec_draft_tokens_total").value - drafted0)
            accepted = int(reg.counter(
                "decode/spec_accepted_tokens_total").value - accepted0)
            cycles = int(reg.counter(
                "decode/spec_cycles_total").value - cycles0)
            accept_rate = (accepted / drafted) if drafted else 0.0
            rec["draft_tokens"] = drafted
            rec["accepted_tokens"] = accepted
            rec["accept_rate"] = round(accept_rate, 4)
            # realized mean spec_k (ISSUE 12): drafted tokens are the
            # per-cycle k summed, so the mean k the engine ACTUALLY ran
            # — equals hps.spec_k statically, walks the committed
            # bounds under the adaptive controller
            rec["spec_cycles"] = cycles
            rec["spec_k_mean"] = (round(drafted / cycles, 3) if cycles
                                  else 0.0)
            try:
                budget_path = os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "BYTE_BUDGET.json")
                with open(budget_path) as f:
                    ratio = json.load(f)["spec"]["max_draft_flops_ratio"][
                        hps.model_family]
                rec["expected_speedup_vs_greedy"] = round(
                    expected_speedup(accept_rate, hps.spec_k, ratio), 3)
            except (OSError, KeyError, ValueError):
                pass  # no committed ratio for this family: rate-only row
        rec.update(info)
        rec.update(_obs_extra())
        print(json.dumps(rec))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_bytes() -> None:
    """BENCH_MODE=bytes: roofline byte accounting for the train step from
    XLA's own cost model — runs on the CPU (cost_analysis is computed
    from the optimized HLO, no execution).

    Compiles the REAL train step at the ask's scale (BENCH_PRESET /
    BENCH_BATCH / BENCH_FAMILY) for the baseline config and each
    byte-diet lever (PERF.md "Byte diet"):

      * ``loss_chunk``  — streaming chunked vocab loss
        (--loss_chunk=BENCH_LOSS_CHUNK, default 25);
      * ``opt_bf16``    — bf16 Adagrad accumulator storage;
      * ``combined``    — both levers together;

    and reports bytes accessed, arithmetic intensity, and each lever's
    reduction vs baseline.  The dp gradient all-reduce lever is reported
    analytically (collective bytes = gradient-tree bytes per step, halved
    by the bf16 wire dtype) — cost_analysis never sees collectives on a
    single-device compile.  The headline value is the BASELINE config's
    bytes/step; reduction_* fields carry the lever claims the byte-budget
    gate (BYTE_BUDGET.json, tests/test_bytes_gate.py) enforces in tier-1.
    """
    from textsummarization_on_flink_tpu.config import HParams
    from __graft_entry__ import train_step_cost as cost_of

    batch = int(os.environ.get("BENCH_BATCH", "16"))
    chunk = int(os.environ.get("BENCH_LOSS_CHUNK", "25"))
    overrides = _preset_overrides()
    overrides.pop("loss_chunk", None)  # the lever axis is swept below
    overrides.pop("opt_state_dtype", None)
    hps0 = HParams(batch_size=batch, compute_dtype="bfloat16", **overrides)

    configs = {
        "baseline": hps0,
        "loss_chunk": hps0.replace(loss_chunk=chunk),
        "opt_bf16": hps0.replace(opt_state_dtype="bfloat16"),
        "combined": hps0.replace(loss_chunk=chunk,
                                 opt_state_dtype="bfloat16"),
    }
    costs = {}
    for name, hps in configs.items():
        sys.stderr.write(f"[bytes] compiling {name} ...\n")
        costs[name] = cost_of(hps)
    base = costs["baseline"]["bytes"]

    # decode rows (ISSUE 7, PERF.md "Decode byte diet"): bytes per
    # emitted token + peak temp of the compiled beam search at the same
    # ask scale — the batch path per loop kind and one step_slots_jit
    # slot chunk (the continuous-serving kernel).  Same single-counted
    # loop-body caveat as the train rows; the committed gate-scale
    # claims live in BYTE_BUDGET.json's decode section.
    from __graft_entry__ import decode_step_cost

    dec_hps = hps0.replace(mode="decode")
    dec_chunk = int(os.environ.get("BENCH_DECODE_CHUNK", "25"))
    decode_rows = {}
    for kind in ("scan", "chunked"):
        sys.stderr.write(f"[bytes] compiling decode/{kind} ...\n")
        c = decode_step_cost(dec_hps, loop=kind,
                             chunk=dec_chunk if kind == "chunked" else None)
        decode_rows[kind] = {
            "bytes": c["bytes"],
            "bytes_per_token": round(c["bytes_per_token"], 1),
            "temp_bytes": c["temp_bytes"],
        }
    sys.stderr.write("[bytes] compiling decode/slot ...\n")
    c = decode_step_cost(dec_hps, path="slot", chunk=dec_chunk)
    decode_rows["slot"] = {
        "bytes": c["bytes"],
        "bytes_per_token": round(c["bytes_per_token"], 1),
        "temp_bytes": c["temp_bytes"],
    }
    # analytic collective bytes from the sharding registry (ISSUE 8):
    # the dp gradient all-reduce moves the registry's per-device
    # reduction set each step (2x on the wire for a ring, but the RATIO
    # is what matters); on a tp mesh (BENCH_MESH) sharded leaves ride
    # the wire as shards
    from textsummarization_on_flink_tpu.parallel import (
        sharding as sharding_lib,
    )

    comms_f32 = sharding_lib.analytic_comms(
        hps0.replace(grad_allreduce_dtype="float32"))
    comms_bf16 = sharding_lib.analytic_comms(
        hps0.replace(grad_allreduce_dtype="bfloat16"))
    _, info = _device_info()
    rec = {
        "metric": "train_step_bytes_accessed",
        "value": base,
        "unit": "bytes",
        "vs_baseline": 0.0,  # the reference publishes no byte accounting
        "levers": {
            name: {
                "bytes": c["bytes"],
                "flops": c["flops"],
                "temp_bytes": c["temp_bytes"],
                "intensity_flops_per_byte": round(
                    c["flops"] / max(c["bytes"], 1.0), 2),
                "reduction_vs_baseline": round(1.0 - c["bytes"] / base, 4),
            } for name, c in costs.items()
        },
        "reduction_loss_chunk": round(
            1.0 - costs["loss_chunk"]["bytes"] / base, 4),
        "reduction_opt_bf16": round(
            1.0 - costs["opt_bf16"]["bytes"] / base, 4),
        "reduction_combined": round(
            1.0 - costs["combined"]["bytes"] / base, 4),
        "grad_allreduce_bytes_f32": comms_f32["dp_wire_bytes"],
        "grad_allreduce_bytes_bf16": comms_bf16["dp_wire_bytes"],
        "decode": decode_rows,
        "decode_chunk": dec_chunk,
        "loss_chunk": chunk,
        "batch": batch,
        "model_family": hps0.model_family,
        "note": "XLA cost_analysis on the optimized HLO (CPU-compiled; "
                "no execution).  Caveats: bytes depend on fusion "
                "decisions, and HloCostAnalysis counts a loop BODY once "
                "(both configs' decoder scans are counted once, so that "
                "cancels in the ratio, but the chunked loss scan's "
                "per-chunk traffic is also single-counted) — treat the "
                "ratios as the cost-model claim; temp_bytes (peak live "
                "temp from memory_analysis) is the loop-independent "
                "evidence the scores value+residual are gone",
    }
    rec.update(info)
    print(json.dumps(rec))


def bench_trainer() -> None:
    """BENCH_MODE=trainer: END-TO-END production-path training
    throughput — the real Trainer.train() over the threaded bucketing
    Batcher, DevicePrefetcher, multi-step dispatch
    (BENCH_SPD=steps_per_dispatch, default 8), windowed metric fetches
    included.  Unlike BENCH_MODE=train (the pure on-device step loop)
    this number pays every real cost a user pays; the gap between the
    two IS the host-side overhead."""
    import shutil
    import tempfile

    import jax

    from textsummarization_on_flink_tpu.config import HParams
    from textsummarization_on_flink_tpu.data.batcher import Batcher
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib

    # default higher than train mode: the timed window deliberately
    # includes the fresh prefetcher's cold start (each train() call
    # builds its own — that ramp IS a real cost of the loop), so enough
    # dispatches must follow to amortize it the way a long run would
    steps = int(os.environ.get("BENCH_STEPS", "120"))
    batch = int(os.environ.get("BENCH_BATCH", "16"))
    spd = int(os.environ.get("BENCH_SPD", "8"))
    # the multi-step executable is specialized per dispatch width k: warm
    # with exactly one full-spd dispatch and round the measured steps to
    # a multiple of spd, so no compile ever lands in the timed window
    warm = spd
    steps = max(steps // spd, 1) * spd
    hps = HParams(batch_size=batch, compute_dtype="bfloat16",
                  steps_per_dispatch=spd, **_preset_overrides())

    tmp = tempfile.mkdtemp(prefix="bench_trainer_")
    try:
        pattern, vocab = _synthetic_dataset(tmp, hps)
        # vocab is sized to hps.vocab_size, so model shapes (and the
        # dominant vocab projection) match BENCH_MODE=train — the gap
        # between the two modes is purely host-side overhead
        assert vocab.size() == hps.vocab_size, (vocab.size(), hps.vocab_size)
        hps = hps.replace(log_root=tmp, exp_name="bench")
        batcher = Batcher(pattern, vocab, hps, single_pass=False)
        trainer = trainer_lib.Trainer(hps, vocab.size(), batcher,
                                      metrics_every=10)
        trainer.train(num_steps=warm)  # compile + queue warm-up
        t0 = time.perf_counter()
        state = trainer.train(num_steps=warm + steps)
        # train() already synced on the final metrics flush; the step
        # fetch closes any remaining gap and doubles as a sanity check
        step_now = int(np.asarray(jax.device_get(state.step)))
        dt = max(time.perf_counter() - t0, 1e-9)
        assert step_now == warm + steps, (step_now, warm, steps)
        samples_per_sec = steps * batch / dt
        dev, info = _device_info()
        flops = (transformer_flops_per_step(hps)
                 if hps.model_family == "transformer"
                 else train_flops_per_step(hps))
        step_time = dt / steps
        rec = {
            "metric": "trainer_e2e_samples_per_sec",
            "value": round(samples_per_sec, 2),
            "unit": "samples/s",
            "vs_baseline": round(samples_per_sec / BASELINE_SAMPLES_PER_SEC, 2),
            "step_time_ms": round(step_time * 1e3, 3),
            "steps_per_dispatch": spd,
            "batch": batch,
            "steps": steps,  # BENCH_STEPS rounded to a multiple of spd
            "warmup_steps": warm,
            "note": "real Trainer loop: batcher + prefetch + dispatch "
                    "+ windowed metric fetches; includes one fresh-"
                    "prefetcher cold start (amortized over `steps`)",
        }
        rec.update(_mfu_fields(dev, info["platform"], flops, step_time))
        rec.update(info)
        rec.update(_obs_extra())
        print(json.dumps(rec))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def child_main() -> None:
    from textsummarization_on_flink_tpu.utils import (
        set_default_compile_cache,
    )

    set_default_compile_cache()  # a child started without the supervisor
    mode = os.environ.get("BENCH_MODE", "train")
    if mode == "decode":
        bench_decode()
    elif mode == "attention":
        bench_attention()
    elif mode == "flash":
        bench_flash()
    elif mode == "input":
        bench_input()
    elif mode == "trainer":
        bench_trainer()
    elif mode == "serve":
        bench_serve()
    elif mode == "bytes":
        bench_bytes()
    elif mode == "train":
        bench_train()
    else:
        print(json.dumps({"metric": f"bench_{mode}", "value": 0.0,
                          "unit": "n/a", "vs_baseline": 0.0,
                          "retryable": False,
                          "error": f"unknown BENCH_MODE={mode!r} (train/"
                                   f"trainer/decode/attention/flash/input/"
                                   f"serve/bytes)"}))
        sys.exit(2)


if __name__ == "__main__":
    if "--serve" in sys.argv[1:]:
        # `python bench.py --serve` == BENCH_MODE=serve; set via env so
        # the supervisor's fingerprint AND the re-exec'd child (which
        # never sees argv) both agree on the mode
        os.environ["BENCH_MODE"] = "serve"
    for arg in sys.argv[1:]:
        # serve-mode sub-flags ride the same env hand-off (the child
        # never sees argv): --serve-mode=continuous|microbatch,
        # --serve-mix=bimodal|buckets
        if arg.startswith("--serve-mode="):
            os.environ["BENCH_MODE"] = "serve"
            os.environ["BENCH_SERVE_MODE"] = arg.split("=", 1)[1]
        elif arg.startswith("--serve-mix="):
            os.environ["BENCH_MODE"] = "serve"
            os.environ["BENCH_SERVE_MIX"] = arg.split("=", 1)[1]
        elif arg.startswith("--serve-tier="):
            os.environ["BENCH_MODE"] = "serve"
            os.environ["BENCH_SERVE_TIER"] = arg.split("=", 1)[1]
        elif arg.startswith("--serve-short-ratio="):
            os.environ["BENCH_MODE"] = "serve"
            os.environ["BENCH_SERVE_SHORT_RATIO"] = arg.split("=", 1)[1]
        elif arg.startswith("--serve-replicas="):
            os.environ["BENCH_MODE"] = "serve"
            os.environ["BENCH_SERVE_REPLICAS"] = arg.split("=", 1)[1]
        elif arg.startswith("--serve-hedge-ms="):
            os.environ["BENCH_MODE"] = "serve"
            os.environ["BENCH_SERVE_HEDGE_MS"] = arg.split("=", 1)[1]
        elif arg.startswith("--serve-zipf="):
            os.environ["BENCH_MODE"] = "serve"
            os.environ["BENCH_SERVE_ZIPF"] = arg.split("=", 1)[1]
        elif arg == "--serve-hier" or arg.startswith("--serve-hier="):
            # `--serve-hier[=N]`: the ISSUE-19 long-document map-reduce
            # workload, N chunks wide (BENCH_HIER_CHUNKS)
            os.environ["BENCH_MODE"] = "serve"
            os.environ["BENCH_SERVE_HIER"] = "1"
            if "=" in arg:
                os.environ["BENCH_HIER_CHUNKS"] = arg.split("=", 1)[1]
        elif arg.startswith("--serve-arena-pages="):
            # `--serve-arena-pages=N`: the ISSUE-20 paged resident
            # state — continuous engine over an N-page arena
            os.environ["BENCH_MODE"] = "serve"
            os.environ["BENCH_SERVE_MODE"] = "continuous"
            os.environ["BENCH_SERVE_ARENA_PAGES"] = arg.split("=", 1)[1]
    if os.environ.get("TS_BENCH_CHILD") == "1":
        child_main()
    else:
        supervise()
