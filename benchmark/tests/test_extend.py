"""A new configuration, traffic mix, cell and per-layer metric over an
existing source kind are added by new files and new entries alone: done
here in a temporary copy, and the added cell rehearses."""
import json
import os
import shutil
import subprocess
import sys

from tests.tiny import BENCH, benchmark_with_held

ROOT = os.path.dirname(BENCH)


def test_add_a_cell_and_a_metric_by_files_alone(tmp_path):
    before = {}
    for dirpath, _, files in os.walk(BENCH):
        for f in files:
            if "__pycache__" not in dirpath:
                p = os.path.join(dirpath, f)
                before[os.path.relpath(p, BENCH)] = open(p, "rb").read()
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "textsummarization_on_flink_tpu"),
               tmp_path / "textsummarization_on_flink_tpu")
    # the held cells' entries (benchmark/held/) go back in the same way:
    # entries alone, their files are still there
    b = json.load(open(benchmark_with_held(tmp_path)))
    held = [w["name"] for w in b["workloads"]][1:]
    nb = tmp_path / "benchmark"
    # a configuration: the pointer-generator with another file of its own
    cfg = json.load(open(nb / "configs" / "pg_see2017.json"))
    cfg["name"] = "pg_other"
    cfg["hparams"]["min_dec_steps"] = 3
    json.dump(cfg, open(nb / "configs" / "pg_other.json", "w"))
    # a traffic mix: only parameters of the one generator (evenly spaced
    # arrivals at another rate, short articles only)
    mix = json.load(open(nb / "traffic" / "news_open_loop.json"))
    mix.update(arrivals="uniform", rate_per_s=9.0)
    json.dump(mix, open(nb / "traffic" / "even_slow.json", "w"))
    # its cell and a metric over a counter nothing read before
    json.dump({"name": "pg_other_even",
               "check": {"sample": {"score": 4, "beam": 1}},
               "limits": {"score_gap": 1e-3, "beam_gap": 1e-3,
                          "compiles_in_window": 0}},
              open(nb / "workloads" / "pg_other_even.json", "w"))
    metric = {"name": "refills_per_s.even", "unit": "1/s",
              "layer": "scheduler (serve/batcher.py, decode/arena.py)",
              "moves": "summary_p99_ms",
              "source": {"kind": "registry", "per_window_s": True,
                         "name": "serve/slot_refills_total"}}
    json.dump(metric, open(nb / "metrics" / "refills_per_s.even.json", "w"))
    b["configs"].append({"name": "pg_other", "source": cfg["source"],
                         "file": "benchmark/configs/pg_other.json",
                         "reduced": [], "why": "throw-away"})
    b["workloads"].append({"name": "pg_other_even", "config": "pg_other",
                           "traffic": "even_slow", "chips": 1,
                           "why": "throw-away"})
    b["end_to_end"].append({"name": "summary_p99_ms", "unit": "ms",
                            "better": "lower", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["pg_other_even"]})
    b["per_layer"].append({"name": "refills_per_s.even", "unit": "1/s",
                           "better": "higher", "source": "program_counter",
                           "layer": metric["layer"],
                           "moves": "summary_p99_ms",
                           "workloads": ["pg_other_even"]})
    json.dump(b, open(tmp_path / "BENCHMARK.json", "w"))
    for cell in ["pg_other_even"] + held:
        p = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", cell,
             "--seed", "4", "--seconds", "2", "--trace", "0", "--rehearse",
             "1"], cwd=str(tmp_path), env=dict(os.environ,
                                               JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        line = json.loads(p.stdout.strip().splitlines()[-1])
        assert line["correct"] is True and line["attempted"] > 0, cell
    # the new metric's reader finds its counter in a run's registry
    sys.path.insert(0, str(nb))
    from harness import readers

    ctx = {"registry0": {"serve/slot_refills_total": 3.0},
           "registry1": {"serve/slot_refills_total": 13.0}, "window_s": 2.0,
           "harness": {}, "trace": None}
    assert readers.read(metric, ctx) == 5.0
    # and no file the benchmark already had was edited
    for rel, data in before.items():
        assert open(nb / rel, "rb").read() == data, rel
