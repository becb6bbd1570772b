#!/usr/bin/env bash
# One-command, no-hardware validation of the whole framework:
#   scripts/repro.sh        # fast tier (~12 min): suite + dryrun + smokes
#   scripts/repro.sh full   # adds the slow test tier (~25 min total)
#
# Uses the virtual 8-device CPU mesh throughout (the chip is reached
# only through `python chip_smoke.py`, one command per chip call).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="$PWD"
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"

echo "== lint (ruff or compileall fallback + tools/tslint AST rules)"
bash scripts/lint.sh

echo "== static analysis self-check (tslint JSON reporter + rule registry)"
# lint.sh already ran the text-mode gate; exercise the reporter paths it
# does NOT touch so a broken --format json / --list-rules fails repro
python -m tools.tslint --baseline tools/tslint/baseline.json --format json \
  > /dev/null
python -m tools.tslint --list-rules > /dev/null

echo "== telemetry smoke (obs registry/spans/exporters)"
python -m pytest tests/test_obs*.py -q -p no:cacheprovider

echo "== chaos smoke (resilience primitives + seeded fault injection)"
# fast, deterministic recovery-path checks (RESILIENCE.md); the full
# TS_FAULTS end-to-end sweeps live in scripts/chaos.sh
python -m pytest tests/test_resilience.py tests/test_chaos.py \
  tests/test_bridge.py -q -p no:cacheprovider

echo "== test suite"
# obs/chaos tests already ran in the smoke steps above — skip the rerun
OBS_SKIP=(--ignore=tests/test_obs.py --ignore=tests/test_obs_integration.py
          --ignore=tests/test_resilience.py --ignore=tests/test_chaos.py
          --ignore=tests/test_bridge.py)
if [ "${1:-fast}" = "full" ]; then
  python -m pytest tests/ -q "${OBS_SKIP[@]}"
else
  python -m pytest tests/ -q -m "not slow" "${OBS_SKIP[@]}"
fi

echo "== driver hooks: entry() trace + 8-device sharded dryrun"
python -c "
import jax, __graft_entry__ as g
fn, args = g.entry()
jax.jit(fn).lower(*args)
print('entry() traces ok')
g.dryrun_multichip(8)"

echo "== one-mesh smoke (dp x tp train + serve on the faked 8-device mesh)"
# the ISSUE-8 registry end to end: unified sharded step at dp=4 x tp=2
# with bf16 gradient wire + loss_chunk + bf16 opt state, then the same
# rows through BOTH serving engines at dp=2 x tp=2 with single-device
# row parity (the committed collective-byte claims live in
# BYTE_BUDGET.json's comms section, enforced in the suite above)
python scripts/mesh_smoke.py

echo "== serve smoke (CollectionSource -> ServingServer -> CollectionSink)"
# the concurrent serving path (SERVING.md) over the 8 synthetic rows,
# BOTH dispatch engines: micro-batch (queue admission, coalescing,
# bucket padding) and continuous — which now runs the ISSUE-11
# DISAGGREGATED path (mixed-length articles through the bucketed
# prefill stage into length-masked slots) — with row-for-row parity
# asserted between the two engines and the prefill telemetry checked
python scripts/serve_smoke.py

echo "== fleet smoke (3 replicas, kill one under load, exactly-once + parity)"
# the ISSUE-13 elastic fleet end to end on a real tiny model: the
# threaded FleetRouter fronts 3 in-process replicas, one is killed
# mid-decode, its residents/queued requests requeue on survivors, and
# the answers stay row-identical to a single-server run (the committed
# virtual-time swap/hedge/kill gates live in SERVE_SLO.json "fleet",
# enforced in the suite above)
python scripts/fleet_smoke.py

echo "== process-fleet smoke (3 OS child processes, SIGKILL mid-decode)"
# the ISSUE-17 process boundary end to end: the same router fronts 3
# SUPERVISED child processes (cli.py serve-replica) over the socket
# transport; one child is SIGKILLed on a real pid mid-decode, its
# orphans requeue on survivors (exactly-once + row parity vs the solo
# run), the victim restarts under supervision and is readmitted
# through the rotation breaker's half-open probe, and the survivors'
# events.jsonl ledgers witness every finish (the committed transport
# overhead ceilings live in SERVE_SLO.json process_fleet, enforced in
# the suite above; the armed serve.proc_kill sweep is in chaos.sh)
python scripts/fleet_smoke.py --transport=proc

echo "== locksan smoke (TS_LOCKSAN=1: runtime lock-order sanitizer armed)"
# the PR-18 dynamic half of tslint's concurrency story: the SAME
# process-fleet smoke (and one armed proc_kill chaos sweep) with every
# serve/resilience lock built through obs/locksan, cross-checked
# against the statically derived lock-order graph — an AB/BA inversion
# raises the typed LockOrderInversionError instead of deadlocking, and
# the smoke's _locksan_gate asserts acquisitions > 0 with ZERO
# inversions (ANALYSIS.md "Concurrency rules")
LG="$(mktemp /tmp/lockgraph.XXXXXX.json)"
python -m tools.tslint --lock-graph "$LG" textsummarization_on_flink_tpu tools
TS_LOCKSAN=1 TS_LOCKSAN_GRAPH="$LG" \
  python scripts/fleet_smoke.py --transport=proc
TS_LOCKSAN=1 TS_LOCKSAN_GRAPH="$LG" TS_FAULTS="serve.proc_kill:1.0:0:1" \
  python scripts/fleet_smoke.py --transport=proc
rm -f "$LG"

echo "== front-door smoke (coalescing + summary cache on a real model)"
# the ISSUE-14 front door end to end: a duplicate-heavy burst coalesces
# onto shared decodes, the warm pass serves byte-identical rows from
# the (content_hash, tier, fingerprint) cache with zero new decodes,
# and the tier axis misses as designed (the enforced zipf/tenant/fleet
# scheduling claims live in SERVE_SLO.json front_door, in the suite)
python scripts/front_door_smoke.py

echo "== hiersum smoke (framed long doc -> map-reduce fan-out -> append dedup)"
# the ISSUE-19 long-document path end to end on a real tiny model: a
# multi-chunk document arrives as framed rows through the pipeline
# stage (transform(hierarchical=True)), fans out chunk-by-chunk over a
# live ServingServer with one reduce pass, then an APPEND frame-set
# re-summarizes the grown document with every pre-append chunk served
# from the front-door cache — only the appended tail + one reduce
# decode (the committed fan-out makespan and cache-hit floor live in
# SERVE_SLO.json hierarchical, enforced in the suite above)
python scripts/hiersum_smoke.py

echo "== speculative-tier smoke (draft init -> spec decode -> exactness)"
# the ISSUE-10 fast path end to end: AAN draft mapped from the full
# model's own params, draft-then-verify decode through the decoder's
# tier surface, token exactness vs the greedy tier asserted (the
# committed FLOPs/state gates live in BYTE_BUDGET.json's spec section,
# enforced in the suite above)
python scripts/spec_smoke.py

echo "== distill-spec smoke (narrow draft distilled -> adaptive spec decode)"
# the ISSUE-12 fast path end to end: a tiny teacher trained on synthetic
# copy data, the NARROW draft (half width + factored vocab head)
# distilled from its greedy outputs through train/distill.DistillTrainer,
# then acceptance-adaptive spec decode asserted token-exact with greedy
# (the committed FLOPs-ratio and acceptance-floor gates live in
# BYTE_BUDGET.json's spec section, enforced in the suite above)
python scripts/spec_smoke.py --distill

echo "== live-plane smoke (/metrics + /healthz + /profile over a continuous run)"
# the ISSUE-9 exposition plane end to end: scrape-vs-render_text byte
# parity, healthz component heartbeats, one uuid's trace timeline
# reconstructed from the unified events.jsonl (trace_summary --request),
# and (ISSUE 16) the /profile phase table + compile-ledger warm set
# scraped off the live run.  TS_SMOKE_OUT keeps the events.jsonl for
# the perf-report stage below.
T="$(mktemp -d)"
trap 'rm -rf "$T"' EXIT
TS_SMOKE_OUT="$T/smoke_events" python scripts/obs_http_smoke.py

echo "== perf-report smoke (span self-time table off the smoke's events)"
# the ISSUE-16 offline attribution view: the same events.jsonl the
# trace timeline came from, aggregated per span name; the serve
# dispatch/prefill spans the run just produced must show up
python scripts/perf_report.py "$T/smoke_events" --json | python -c "
import json, sys
rep = json.load(sys.stdin)
rows = rep['spans']
names = {row['name'] for row in rows}
assert {'serve/dispatch', 'serve/prefill'} <= names, names
print(f'perf report OK: {len(rows)} span rows ({sorted(names)})')"

echo "== bench smokes (CPU, tiny): train / input / decode / serve"
for mode in train input decode serve; do
  BENCH_MODE="$mode" BENCH_PLATFORM=cpu BENCH_PRESET=tiny BENCH_STEPS=2 \
    BENCH_SECONDS=0.5 BENCH_SERVE_REQS=8 BENCH_SERVE_CONCURRENCY=4 \
    BENCH_ATTEMPTS=1 \
    python bench.py 2>/dev/null | tail -1
done

echo "== continuous-mode serve load smoke (bimodal mix)"
# the ISSUE-6 engine under the straggler workload it exists for: slot
# occupancy + refills reported alongside p50/p99 (SERVE_SLO.json holds
# the enforced scheduling claim; this proves the real-model path runs)
BENCH_MODE=serve BENCH_PLATFORM=cpu BENCH_PRESET=tiny \
  BENCH_SERVE_MODE=continuous BENCH_SERVE_MIX=bimodal \
  BENCH_SERVE_REQS=8 BENCH_SERVE_CONCURRENCY=4 BENCH_ATTEMPTS=1 \
  python bench.py 2>/dev/null | tail -1

echo "== prefill/decode disaggregation smoke (short-heavy bimodal mix)"
# the ISSUE-11 path under the load it exists for: a NON-default
# short-request ratio (7/8 short — fingerprinted via the short_ratio
# axis) through the continuous engine, so the row carries
# prefill_total > 0 and the bucketed-prefill + length-masked slot
# machinery runs end to end on a real model (the enforced claims live
# in BYTE_BUDGET.json decode.length_axis/prefill and SERVE_SLO.json
# disaggregated, both in the suite above)
BENCH_MODE=serve BENCH_PLATFORM=cpu BENCH_PRESET=tiny \
  BENCH_SERVE_MODE=continuous BENCH_SERVE_MIX=bimodal \
  BENCH_SERVE_SHORT_RATIO=0.875 \
  BENCH_SERVE_REQS=8 BENCH_SERVE_CONCURRENCY=4 BENCH_ATTEMPTS=1 \
  python bench.py 2>/dev/null | tail -1

echo "== roofline (XLA cost-model floors, tiny config)"
python scripts/roofline.py --configs train_tiny

echo "== decode-bytes smoke (backpointer beam-search byte accounting)"
# the ISSUE-7 decode byte diet's cost path end to end: compiles the
# restructured search at tiny scale and prints bytes/token + peak temp
# (the committed gate-scale claims live in BYTE_BUDGET.json's decode
# section, enforced by tests/test_bytes_gate.py in the suite above)
python scripts/roofline.py --configs decode_bytes_tiny

echo "repro OK"
