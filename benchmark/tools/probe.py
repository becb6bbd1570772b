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


def plant_token_swap():
    import jax.numpy as jnp

    from textsummarization_on_flink_tpu.models import pointer_generator as m

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
    ap.add_argument("--pair", type=int, default=0,
                    help="1: seed i goes with rate i, not with every rate")
    args = ap.parse_args()

    bench, cell, cfg, mix, cell_file = bench_run.load_cell(args.workload)
    if args.rehearse:
        bench_run.apply_rehearsal(cfg, mix, cell_file)
    bench_run.prepare_process(args.rehearse)
    from harness import correct, traffic

    if args.program_dtype:
        cfg["hparams"]["compute_dtype"] = args.program_dtype
    if args.fault == "token_swap":
        plant_token_swap()
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    seeds = [int(s) for s in args.seeds.split(",")]
    clock = cfg["init"].get("summary_clock")
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
                    from harness import weights

                    off = [len(r.decoded_words) + 1 - int(weights.length_code(
                        clock, int(a.ids[0]))) for a, r in run.finished
                        if len(r.decoded_words) < cfg["hparams"][
                            "max_dec_steps"]]
                    if off:
                        rec["served_minus_coded_tokens"] = {
                            "mean": sum(off) / len(off), "min": min(off),
                            "max": max(off)}
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
