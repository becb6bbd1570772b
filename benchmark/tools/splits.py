"""Builder's tool (chip): ONE traced run of a served cell through the
harness's own `run_cell`, with the sums that say the per-layer splits
close (idle by host phase against the device's idle share, the slot
chunk's device time by named scope against the chunk), the stage
histogram against the latency histogram, the capture's heaviest
instructions and the programs' device time.  Everything is read from what
`run_cell` hands back (its result line and the readers' context).

    python benchmark/tools/splits.py --workload pg_serve_steady --seed 0 \
        --seconds 45 [--out chiprun_out/splits.json] [--dump DIR] \
        [--trace 1] [--rehearse 0]

Prints the run's result line with a "splits" object in it.  `--dump DIR`
also writes the capture's host phases and program runs (`capture.json`:
small, for reading a tick by hand) and the compiled slot step's text
(`slot_step.hlo.txt`).  Never a measurement of the benchmark.  `--trace
0` leaves the capture out (the trace metrics with it) and reads the stage
clock of an undisturbed run: stopping a capture holds the server up for
seconds, and a traced run's readers wait longer for it.  `--rehearse 1`
walks the same path at tiny shapes on the CPU (no capture there: the
trace metrics stay out, the stage sums are still read).
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

import run as bench_run  # noqa: E402

STAGES = ("queue", "prefill", "slot_wait", "resident", "harvest")


def stage_sums(snapshot):
    """Whole-run figures (warm-up and drain included: the same requests
    on both sides) of the stage histogram and of the latency histogram,
    from the registry as the run left it: the sums that must agree, and
    mean / p50 / p95 of each."""
    from harness import readers

    def hist(name, **labels):
        return snapshot.get(readers.series_key({"name": name,
                                                "labels": labels}))

    by = {s: hist("serve/request_stage_seconds", stage=s) for s in STAGES}
    e2e = hist("serve/e2e_latency_seconds")
    if e2e is None or not all(by.values()):
        return None

    def stats(x):
        return {"mean_s": x["sum"] / max(1, x["count"]),
                "p50_s": readers.hist_percentile(x, 50),
                "p95_s": readers.hist_percentile(x, 95)}

    chunks = hist("serve/request_resident_chunks")
    return {"stages_s": {s: x["sum"] for s, x in by.items()},
            "stages_sum_s": sum(x["sum"] for x in by.values()),
            "e2e_sum_s": e2e["sum"], "requests": e2e["count"],
            "stats": dict({s: stats(x) for s, x in by.items()},
                          e2e=stats(e2e)),
            "resident_chunks_mean": chunks["sum"] / max(1, chunks["count"])
            if chunks is not None else None}


def top_instructions(capture, paths, program, n=16):
    """The n instructions with most self time inside the runs of
    `program` on the first device, as [instruction, the tail of its
    op_name, ms a run]: what a builder reads to see which operation a
    scope's (or no scope's) milliseconds are."""
    from harness import splits

    dev = splits._first_device((capture or {}).get("planes", {}))
    if dev is None or not program:
        return []
    calls, ops = splits.program_ops(dev, program)
    if not calls:
        return []
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
    bench_run.prepare_process(args.rehearse, args.trace)
    from harness import splits

    out = bench_run.run_cell(bench, cell, cfg, mix, cell_file, args.seed,
                             args.seconds, trace=args.trace,
                             rehearse=args.rehearse)
    line, ctx = out["line"], out["ctx"]
    capture, hlo = ctx.get("capture"), ctx.get("slot_step_hlo")
    paths = splits.scope_map(hlo or "")
    kinds, program = {}, ""
    for m in bench_run.metrics_of(bench, args.workload, "per_layer"):
        src = bench_run._load("metrics", m["name"] + ".json")["source"]
        kinds[m["name"]] = src["kind"]
        if src["kind"] == "trace_scope":
            program = src["program"]

    def metric(name):
        return line.get("metrics", {}).get(name, {}).get("value")

    def total(kind):
        vals = [metric(n) for n, k in kinds.items()
                if k == kind and metric(n) is not None]
        return sum(vals) if vals else None

    line["splits"] = {
        "idle_sum_pct": total("trace_phase"),
        "device_idle_pct": metric("device_idle.steady"),
        "scope_sum_ms": total("trace_scope"),
        "slot_chunk_device_ms": metric("slot_chunk_device_ms.steady"),
        "stage_sums": stage_sums(ctx["registry1"]),
        "phase_events": len(splits.dispatch_thread(
            (capture or {}).get("threads", []))),
        "scoped_instructions": len(paths),
        "top_instructions": top_instructions(capture, paths, program),
        "programs": out["programs"],
        "e2e": out["e2e"],
    }
    if args.dump and capture:
        os.makedirs(args.dump, exist_ok=True)
        dev = splits._first_device(capture["planes"]) or {}
        with open(os.path.join(args.dump, "capture.json"), "w",
                  encoding="utf-8") as f:
            json.dump({"threads": capture["threads"],
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
