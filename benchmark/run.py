"""One command, one cell, one run:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that builds the cell's system from the program's normal
entry points, warms the cell's own shapes (set-up), measures a window of
--seconds, checks what the timed path produced against the plain
reference, and prints ONE JSON object as the last line of standard
output.  It measures only on a TPU whose `device_kind` is in the peaks
table (benchmark/harness/peaks.py); anywhere else it exits non-zero and
prints no result.  `--rehearse 1` is not a measurement: it runs the
cell's control flow end to end at tiny shapes on the CPU and prints a
line marked as a rehearsal with no device metric in it.

Everything a cell is made of is data: BENCHMARK.json names the cell, its
configuration (benchmark/configs/), its traffic mix (benchmark/traffic/),
its own file (benchmark/workloads/: the limits of `correct`) and its
per-layer metrics (benchmark/metrics/); what the harness knows of a
model family is one module under benchmark/harness/families/, found by
the configuration's "family".
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (BENCH, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


BENCHMARK_JSON = os.path.join(ROOT, "BENCHMARK.json")


def load_cell(name: str):
    """(BENCHMARK.json, cell entry, config file, traffic file, cell file)."""
    with open(BENCHMARK_JSON, encoding="utf-8") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"]), encoding="utf-8") as f:
        cfg = json.load(f)
    mix = _load("traffic", cell["traffic"] + ".json")
    return bench, cell, cfg, mix, _load("workloads", name + ".json")


def metrics_of(bench, cell_name: str, group: str):
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def apply_rehearsal(cfg, mix, cell_file):
    tiny = _load("rehearse.json")
    cfg["hparams"].update({k: v for k, v in tiny["hparams"].items()
                           if k in cfg["hparams"]})
    for role, over in tiny["deployment"].items():
        if role in cfg["deployment"]:
            cfg["deployment"][role].update(over)
    mix.update({k: v for k, v in tiny["traffic"].get(mix["kind"], {}).items()})
    for k, v in tiny["article"].items():
        mix["article"][k] = v
    if "abstract" in mix:
        mix["abstract"].update(tiny["abstract"])
    if "summary" in mix:
        mix["summary"].update(tiny["summary"])
    if "summary_clock" in cfg["init"]:
        cfg["init"].update(tiny["init"])
    # the rehearsal's sample caps the cell's own: a cell that samples no
    # search of the reference's (`beam` 0) rehearses none either
    asked = cell_file["check"].get("sample", {})
    cell_file["check"].update(tiny["check"])
    cell_file["check"]["sample"] = {
        k: min(v, int(asked.get(k, v)))
        for k, v in tiny["check"]["sample"].items()}
    # then the configuration's own rehearsal sizes: a family with widths
    # of its own shrinks them in its own file
    own = cfg.get("rehearse", {})
    cfg["hparams"].update(own.get("hparams", {}))
    for role, over in own.get("deployment", {}).items():
        cfg["deployment"].setdefault(role, {}).update(over)
    cfg["init"].update(own.get("init", {}))


def build_native_bridge() -> None:
    """The program's native reader of chunk files (its documented build
    step, `python -m textsummarization_on_flink_tpu.native.build`): a
    checkout holds no binary, so the first run there builds it, inside
    the checkout, as part of set-up.  Without it the Batcher falls back
    to Python and a training cell turns host-bound (PERF.md): that
    fallback is another system and is never measured under a cell's
    name, so a build that fails ends the run."""
    from textsummarization_on_flink_tpu.native import build as native_build
    from textsummarization_on_flink_tpu.pipeline import bridge

    native_build.build()
    if not bridge.native_available():
        raise RuntimeError("the native bridge was built but does not load")


def run_cell(bench, cell, cfg, mix, cell_file, seed: int, seconds: float,
             trace: int = 0, rehearse: int = 0, hooks=None):
    """One run of one cell from its loaded (by a tool: altered) files.
    Returns a dict: `line` (the result line), and beside it what the
    builder's tools read (`e2e`, `harness`, `run`, `read_numbers`,
    `programs`).  Raises SystemExit with a code other than 0 where the
    run cannot measure."""
    import jax

    from harness import cells, correct, readers, splits, traffic
    from harness import trace as trace_lib
    from harness.compile_meter import CompileMeter
    from harness.peaks import peaks_for

    devices = jax.devices()
    dev = devices[0]
    chips = int(cell["chips"])
    peaks = None
    if not rehearse:
        if dev.platform != "tpu":
            print(f"no accelerator: jax's first device is {dev.platform!r}; "
                  f"this benchmark measures on a TPU only", file=sys.stderr)
            raise SystemExit(3)
        if len(devices) < chips:
            print(f"{len(devices)} chip(s) found, the cell needs {chips}",
                  file=sys.stderr)
            raise SystemExit(3)
        peaks = peaks_for(dev.device_kind)  # unknown kind: an error
    meter = CompileMeter().install()
    work = tempfile.mkdtemp(prefix="bench-")
    setup = {}
    hooks = dict(hooks or {})
    hooks["setup_done"] = lambda: setup.setdefault(
        "s", time.perf_counter() - T_PROCESS)
    tracer_args = ({"dir": os.path.join(work, "trace")}
                   if trace and not rehearse else None)
    kind = mix["kind"]
    name = cell["name"]
    try:
        if kind == "train_job":
            run, ctx = cells.run_train(cfg, mix, seed, seconds, work, meter,
                                       tracer_args, hooks)
            attempted, failed = run.steps, 0
            e2e = {"train_tokens_per_s": run.tokens / run.window_s}
        elif kind == "open_loop":
            run, ctx = cells.run_serve(cfg, mix, seed, seconds, work, meter,
                                       tracer_args, hooks)
            attempted, failed = run.attempted, run.failed
            e2e = {"summary_p50_ms": traffic.percentile(run.latencies_ms, 50),
                   "summary_p95_ms": traffic.percentile(run.latencies_ms, 95)}
            late = run.lateness_ms or [0.0]
            ctx["harness"].update(
                gen_late_p95_ms=traffic.percentile(late, 95),
                gen_late_max_ms=max(late))
        else:
            raise SystemExit(f"unknown traffic kind {kind!r}")
        e2e["setup_s"] = setup["s"]
        # the peak is read once the window has closed and before the
        # reference runs: a process's peak never falls again
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:chips])
        ctx["peaks"] = peaks
        ctx["trace"] = None
        tracer = ctx.pop("tracer")
        if tracer is not None:
            # the capture itself (device lines and the host's phases) for
            # the readers that split it, and its reduction for the rest
            ctx["capture"] = splits.load(tracer.log_dir)
            ctx["trace"] = trace_lib.reduce(
                ctx["capture"]["planes"], tracer.window_s, chips,
                phases=tracer.phases, sync_epoch_ns=tracer.sync_epoch_ns)
        # ---- correct: the timed path's output against the reference ----
        check = cell_file["check"]
        if kind == "train_job":
            def read_numbers(**kw):
                return correct.train_numbers(cfg, seed, run,
                                             int(check["block"]), **kw)
        else:
            words = traffic.Words(int(cfg["hparams"]["vocab_size"]),
                                  mix["article"])

            def read_numbers(**kw):
                return correct.serve_numbers(cfg, seed, run.finished, words,
                                             check["sample"], **kw)
        numbers = read_numbers()
        detail = numbers.pop("_detail")
        numbers["compiles_in_window"] = ctx["compiles_in_window"]
        ok, compared = correct.judge(
            numbers, cell_file["limits"],
            extra_ok=attempted > 0 and (kind == "train_job"
                                        or len(run.finished) > 0))
    finally:
        shutil.rmtree(work, ignore_errors=True)

    short = {k: [v["value"], v["limit"]] for k, v in compared.items()}
    out = {"e2e": e2e, "harness": ctx["harness"], "run": run,
           "read_numbers": read_numbers, "detail": detail,
           "words": None if kind == "train_job" else words,
           "errors": ctx.get("errors"), "programs": None, "ctx": ctx}
    if rehearse:
        out["line"] = {"rehearsal": True, "workload": name,
                       "platform": dev.platform, "correct": ok,
                       "attempted": attempted, "failed": failed,
                       "compared": short}
        return out
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, name, group):
        if group == "end_to_end":
            value = e2e.get(m["name"])
        else:
            value = readers.read(_load("metrics", m["name"] + ".json"), ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # the allocator's peak counts live buffers and code, not the running
    # program's scratch (PERF.md): XLA's own figure for the largest
    # program of the window is added to it
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips,
              "memory_peak_bytes": int(peak) + int(ctx["program_temp_bytes"]),
              "memory_stats_peak_bytes": int(peak),
              "program_temp_bytes": int(ctx["program_temp_bytes"])}
    line = {"correct": ok, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if trace:
        tr = ctx["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": [[n.split(" = ")[0][:80], s]
                                            for n, s in tr["device_ops"]],
                             "idle_gaps": tr["idle_gaps"]}
        out["programs"] = {k: v for k, v in sorted(
            tr["programs"].items(), key=lambda kv: -kv[1]["total_s"])[:12]}
    # what the harness itself saw of the run (the driver ignores the key;
    # the next builder reads it where a run reads far off)
    line["harness"] = ctx["harness"]
    line["compared"] = short  # each number beside its limit, last
    out["line"] = line
    return out


def prepare_process(rehearse: int = 0, trace: int = 0) -> None:
    """What has to be settled before jax is imported: the platform of a
    rehearsal, the compile cache, the program and its native bridge."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if trace:
        # named scopes are metadata, which the compile cache leaves out of
        # its key: without this a cached executable carries the op_names
        # of whatever build compiled it (PERF.md section 6, PR 25)
        os.environ.setdefault(
            "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY", "1")
    # the compile cache: where the machine says, else one fixed path in
    # the checkout (the program's own helper leaves a set variable alone)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        import textsummarization_on_flink_tpu  # noqa: F401
    except ImportError as e:
        print(f"the program is not in this checkout: {e}", file=sys.stderr)
        raise SystemExit(4)
    try:
        build_native_bridge()
    except (OSError, RuntimeError, subprocess.CalledProcessError) as e:
        print(f"the program's native bridge cannot be built or loaded "
              f"({e}): nothing is measured", file=sys.stderr)
        raise SystemExit(5)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    args = ap.parse_args(argv)

    bench, cell, cfg, mix, cell_file = load_cell(args.workload)
    if args.rehearse:
        apply_rehearsal(cfg, mix, cell_file)
    try:
        prepare_process(args.rehearse, args.trace)
        out = run_cell(bench, cell, cfg, mix, cell_file, args.seed,
                       args.seconds, args.trace, args.rehearse)
    except SystemExit as e:
        if isinstance(e.code, int):
            return e.code
        raise
    line = out["line"]
    print(json.dumps({"detail": out["detail"], "errors": out["errors"]},
                     default=float), file=sys.stderr)
    for k, (v, lim) in line["compared"].items():
        print(f"compared {k}: value {v} limit {lim}", file=sys.stderr)
    print(json.dumps(line, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
