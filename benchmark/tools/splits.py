"""Builder's tool (chip): ONE traced run of a served cell through the
harness's own `run_cell`, with the twelve per-layer metrics of
benchmark/proposed/trace_splits.json read beside the accepted ones
(harness/splits.py: idle time by host phase, the slot chunk's device time
by named scope) and the three sums that say the splits close.  They are
not in BENCHMARK.json because the harness cannot reach a new reader kind
without an edit to files this PR may not touch (the file says which); until a
benchmark PR makes those edits this tool is how the splits are read.

    python benchmark/tools/splits.py --workload pg_serve_steady --seed 0 \
        --seconds 45 [--out chiprun_out/splits.json] [--dump DIR] \
        [--trace 1] [--rehearse 0]

Prints the run's result line with the further metrics in it and a
"splits" object: the sums, the stage histogram against the latency
histogram, the capture's size, the programs' device time.  `--dump DIR`
also writes the capture's host phases and program runs (`capture.json`:
small, for reading a tick by hand) and the compiled slot step's text
(`slot_step.hlo.txt`).  Never a measurement of the benchmark:
metadata is made part of the compile cache's key (so that the compiled
slot step's text carries THIS build's op_names), which makes set-up cold
once, and two of the harness's names are wrapped to keep what `run_cell`
throws away — the capture and the server.  `--trace 0` leaves the capture
out (the trace metrics with it) and reads the stage clock of an
undisturbed run: stopping a capture holds the server up for seconds, and
a traced run's readers wait longer for it.  `--rehearse 1` walks the same
path at tiny shapes on the CPU (no capture there: the trace metrics stay
out, the stage sums and the scope map are still read).
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

# before jax is imported: see harness/splits.py on stale op_names
os.environ.setdefault("JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY", "1")

import run as bench_run  # noqa: E402

STAGES = ("queue", "prefill", "slot_wait", "resident", "harvest")


def keep_capture_and_server(kept):
    from harness import splits
    from harness import trace as trace_lib
    from textsummarization_on_flink_tpu.serve import server as server_mod

    real_load = trace_lib.load

    def load_and_keep(log_dir):
        planes = real_load(log_dir)
        kept["capture"] = {"planes": planes,
                           "threads": splits.host_threads(log_dir)}
        kept["xplane_bytes"] = os.path.getsize(
            trace_lib.newest_xplane(log_dir))
        return planes

    trace_lib.load = load_and_keep

    class KeptServer(server_mod.ServingServer):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            kept["server"] = self

    server_mod.ServingServer = KeptServer


def stage_sums(reg):
    """Whole-run figures (warm-up and drain included: the same requests
    on both sides) of the stage histogram and of the latency histogram:
    the sums that must agree, and mean / p50 / p95 of each."""
    h = reg.get("serve/request_stage_seconds")
    e2e = reg.get("serve/e2e_latency_seconds")
    if h is None or e2e is None:
        return None

    def stats(x):
        return {"mean_s": x.sum / max(1, x.count),
                "p50_s": x.percentile(50), "p95_s": x.percentile(95)}

    by = {s: h.labels(stage=s) for s in STAGES}
    chunks = reg.get("serve/request_resident_chunks")
    return {"stages_s": {s: x.sum for s, x in by.items()},
            "stages_sum_s": sum(x.sum for x in by.values()),
            "e2e_sum_s": e2e.sum, "requests": e2e.count,
            "stats": dict({s: stats(x) for s, x in by.items()},
                          e2e=stats(e2e)),
            "resident_chunks_mean": chunks.sum / max(1, chunks.count)
            if chunks is not None else None}


def top_instructions(capture, paths, program, n=16):
    """The n instructions with most self time inside the runs of
    `program` on the first device, as [instruction, the tail of its
    op_name, ms a run]: what a builder reads to see which operation a
    scope's (or no scope's) milliseconds are."""
    from harness import splits

    dev = splits._first_device((capture or {}).get("planes", {}))
    if dev is None:
        return []
    calls, ops = splits.program_ops(dev, program)
    total = {}
    for name, _, ns in ops:
        key = splits.instruction(name)
        total[key] = total.get(key, 0.0) + ns / 1e6 / calls
    return [[k, "/".join(paths.get(k, [])[-3:]), v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--dump", default="")
    ap.add_argument("--trace", type=int, default=1)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args()

    bench, cell, cfg, mix, cell_file = bench_run.load_cell(args.workload)
    if args.rehearse:
        bench_run.apply_rehearsal(cfg, mix, cell_file)
    bench_run.prepare_process(args.rehearse)
    from harness import splits

    kept = {}
    keep_capture_and_server(kept)
    out = bench_run.run_cell(bench, cell, cfg, mix, cell_file, args.seed,
                             args.seconds, trace=args.trace,
                             rehearse=args.rehearse)
    line = out["line"]
    line.setdefault("metrics", {})
    server = kept.get("server")
    compiled = getattr(server, "compiled_slot_step", None)
    hlo = compiled().as_text() if compiled else None
    paths = splits.scope_map(hlo or "")
    ctx = {"capture": kept.get("capture"), "slot_step_hlo": hlo,
           "_scope_map": paths}
    with open(os.path.join(BENCH, "proposed", "trace_splits.json"),
              encoding="utf-8") as f:
        proposed = json.load(f)
    cells = {e["name"]: e["workloads"] for e in proposed["per_layer"]}
    got = {}
    for m in proposed["metrics"]:
        if args.workload not in cells[m["name"]]:
            continue
        value = splits.read(m["source"], ctx)
        if value is not None:
            got[m["name"]] = value
            line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}

    def total(kind):
        vals = [got[m["name"]] for m in proposed["metrics"]
                if m["source"]["kind"] == kind and m["name"] in got]
        return sum(vals) if vals else None

    def metric(name):
        return line["metrics"].get(name, {}).get("value")

    line["splits"] = {
        "idle_sum_pct": total("trace_phase"),
        "device_idle_pct": metric("device_idle.steady"),
        "scope_sum_ms": total("trace_scope"),
        "slot_chunk_device_ms": metric("slot_chunk_device_ms.steady"),
        "stage_sums": stage_sums(server.registry) if server else None,
        "xplane_bytes": kept.get("xplane_bytes"),
        "phase_events": len(splits.dispatch_thread(
            (kept.get("capture") or {}).get("threads", []))),
        "scoped_instructions": len(paths),
        "top_instructions": top_instructions(
            kept.get("capture"), paths, "^jit_step_slots(_paged)?_jit$"),
        "programs": out["programs"],
        "e2e": out["e2e"],
    }
    if args.dump and kept.get("capture"):
        os.makedirs(args.dump, exist_ok=True)
        dev = splits._first_device(kept["capture"]["planes"]) or {}
        with open(os.path.join(args.dump, "capture.json"), "w",
                  encoding="utf-8") as f:
            json.dump({"threads": kept["capture"]["threads"],
                       "modules": dev.get("XLA Modules", [])}, f)
        with open(os.path.join(args.dump, "slot_step.hlo.txt"), "w",
                  encoding="utf-8") as f:
            f.write(hlo or "")
    text = json.dumps(line, default=float)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
