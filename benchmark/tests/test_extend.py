"""A new configuration, traffic mix, cell and per-layer metric over an
existing source kind, a new MODEL FAMILY with a parameter type, a
rehearsal block and a count of its own, a SERVING CELL OF A FAMILY WITH
NO SUMMARY CLOCK, and a cell of a CONFIGURATION THAT STORES BFLOAT16
PARAMETERS are added by new files and new entries alone: done here in
temporary copies; the added cells rehearse, and the last two pass the
copy's own control, fault and file tests."""
import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

from tests.tiny import BENCH, benchmark_with_held

ROOT = os.path.dirname(BENCH)


def _copy(tmp_path):
    """A temporary checkout: benchmark/ copied, the program linked.
    Returns every file of benchmark/ as it is, for the walk at the end."""
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
    return before


def _rehearse(tmp_path, cell, correct=True):
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell,
         "--seed", "4", "--seconds", "2", "--trace", "0", "--rehearse",
         "1"], cwd=str(tmp_path), env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is correct and line["attempted"] > 0, line
    return line


def test_add_a_cell_and_a_metric_by_files_alone(tmp_path):
    before = _copy(tmp_path)
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
                          "beam_gap_median": 1e-3,
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
        _rehearse(tmp_path, cell)
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


READ_THE_NEW_METRIC = """
import json, sys
sys.path[:0] = ["benchmark", "."]
import run as bench_run
from harness import readers
from tests.test_splits import HLO, capture
bench, cell, cfg, mix, cell_file = bench_run.load_cell("pg_third_even")
bench_run.apply_rehearsal(cfg, mix, cell_file)
occ = {"count": 4, "sum": 2.0, "buckets": [1.0], "counts": [4, 0],
       "min": 0.5, "max": 0.5}
ctx = {"capture": capture(), "slot_step_hlo": HLO, "family": cfg["family"],
       "hparams": cfg["hparams"], "harness": {},
       "deployment": {"slots": 4, "chunk": 5}, "registry0": {},
       "registry1": {"serve/slot_occupancy": occ},
       "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e6}}
spec = bench_run._load("metrics", "third_rows_roofline.even.json")
print(json.dumps({"value": readers.read(spec, ctx),
                  "hidden_dim": cfg["hparams"]["hidden_dim"],
                  "slots": cfg["deployment"]["serve"]["serve_slots"],
                  "units": cfg["init"]["summary_clock"]["units"],
                  "dtype": str(__import__("harness.weights", fromlist=["w"])
                               .param_dtype(cfg))}))
"""


def test_add_a_model_family_by_files_alone(tmp_path):
    """The case the family modules exist for: a THIRD family (its module
    a copy of the pointer-generator's under another name, with a count of
    its own), a configuration of it that states its parameter type and
    its own rehearsal sizes, a cell, and a roofline-by-scope metric over
    the new count: files and entries only, and the cell rehearses."""
    before = _copy(tmp_path)
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    nb = tmp_path / "benchmark"
    fam = open(nb / "harness" / "families" / "pointer_generator.py").read()
    fam += '''

def count_vocab_rows(hp, dep, ctx):
    """One read of the occupied residents' vocabulary rows a step."""
    from harness import readers

    rows = readers.occupied_slots(ctx) * int(hp["beam_size"])
    return {"flops": 0.0, "bytes": float(
        rows * int(hp["vocab_size"]) * 4 * int(dep["chunk"]))}
'''
    with open(nb / "harness" / "families" / "pg_third.py", "w") as f:
        f.write(fam)
    cfg = json.load(open(nb / "configs" / "pg_see2017.json"))
    cfg.update(name="pg_third", family="pg_third", param_dtype="float32",
               rehearse={"hparams": {"hidden_dim": 24, "emb_dim": 12},
                         "deployment": {"serve": {"serve_slots": 3}},
                         "init": {"summary_clock": dict(
                             cfg["init"]["summary_clock"], units=3,
                             gain=12.0, step=0.1, phase=0.01, codes=6,
                             min_tokens=3)}})
    json.dump(cfg, open(nb / "configs" / "pg_third.json", "w"))
    mix = json.load(open(nb / "traffic" / "news_open_loop.json"))
    mix.update(arrivals="uniform", rate_per_s=9.0)
    json.dump(mix, open(nb / "traffic" / "even_slow.json", "w"))
    json.dump({"name": "pg_third_even",
               "check": {"sample": {"score": 4, "beam": 1}},
               "limits": {"score_gap": 1e-3, "beam_gap": 1e-3,
                          "beam_gap_median": 1e-3,
                          "compiles_in_window": 0}},
              open(nb / "workloads" / "pg_third_even.json", "w"))
    layer = "models and kernels (models/, ops/)"
    metric = {"name": "third_rows_roofline.even", "unit": "%",
              "layer": layer, "moves": "summary_p95_ms",
              "source": {"kind": "scope_roofline", "count": "vocab_rows",
                         "program": "^jit_step_slots(_paged)?_jit$",
                         "scope": "topk"}}
    json.dump(metric,
              open(nb / "metrics" / "third_rows_roofline.even.json", "w"))
    b["configs"].append({"name": "pg_third", "source": cfg["source"],
                         "file": "benchmark/configs/pg_third.json",
                         "reduced": [], "why": "throw-away"})
    b["workloads"].append({"name": "pg_third_even", "config": "pg_third",
                           "traffic": "even_slow", "chips": 1,
                           "why": "throw-away"})
    for m in b["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("pg_third_even")
    b["per_layer"].append({"name": metric["name"], "unit": "%",
                           "better": "higher", "source": "device_trace",
                           "layer": layer, "moves": "summary_p95_ms",
                           "workloads": ["pg_third_even"]})
    json.dump(b, open(tmp_path / "BENCHMARK.json", "w"))
    _rehearse(tmp_path, "pg_third_even")
    # the new metric reads a capture through the new family's count, at
    # the configuration's own rehearsal sizes and parameter type
    p = subprocess.run([sys.executable, "-c", READ_THE_NEW_METRIC],
                       cwd=str(tmp_path), capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    # 2 occupied x beam 2 x 64 words x 4 B x 5 steps = 5 120 B a run:
    # 5.12 ms at 1 MB/s, over topk's 37.5 ms a run
    assert abs(got.pop("value") - 5.12 / 37.5 * 100.0) < 1e-9
    assert got == {"hidden_dim": 24, "slots": 3, "units": 3,
                   "dtype": "float32"}
    for rel, data in before.items():
        assert open(nb / rel, "rb").read() == data, rel


# Limits of the throw-away clockless cells, set from readings here on the
# CPU in float32 (PR 34; never a chip's).  `score_gap`: sound 4.4e-7 at
# the middle size and 4e-8 to 2.4e-7 rehearsed, the bfloat16 reference
# 2.8e-3 (2.5e-3 since PR 36 repaired the transformer's control, on
# another article: a widest gap), a swapped token 0.59 to 1.23.  The beam
# numbers: sound under 3.3e-7, one altered answer 0.53, five 0.67 (median
# 0.56), a swapped token 0.21 to 0.75.
CLOCKLESS_LIMITS = {"score_gap": 3e-4, "compiles_in_window": 0}
CLOCKLESS_BEAM_LIMITS = {"beam_gap": 0.05, "beam_gap_median": 0.01}
# The cell of the configuration that STORES BFLOAT16 PARAMETERS holds the
# same limit, because its sound reference is float32 all the same (CPU,
# PR 36; never a chip's).  `score_gap`: sound 3.4e-7 at the middle size
# (the reference against its own search: the limit is 870 times that),
# the control 3.7e-3 there (bfloat16 activations over the same leaves:
# 12 times the limit), a swapped token 0.34 to 0.78 rehearsed; and the
# PROGRAM, which takes its activations' type from the leaves it is
# handed, 8.2e-4 to 2.4e-3 rehearsed on 4 seeds, where the bfloat16
# reference scores the same tokens 5.4e-4 to 2.4e-3 off: not correct, as
# a program that lowers its activations has to read.
BF16_CELL = "tf_bf16_served"
BF16_LIMITS = {"score_gap": 3e-4, "compiles_in_window": 0}
CLOCKLESS_CELLS = {"tf_served_b0": 0, "tf_served_b1": 1}


@pytest.fixture(scope="module")
def clockless_copy(tmp_path_factory):
    """ONE temporary copy for the proofs below: a family module that
    offers no `wire` (the transformer's, which the program serves through
    the slot engine) under two throw-away configurations with no
    `init.summary_clock` and a `rehearse` block of their own, one storing
    float32 parameters (`tf_served`) and one bfloat16 (`tf_bf16`); mixes
    with no `summary` block, so that every summary runs to
    `max_dec_steps`; and three cells (a pair of configuration and traffic
    appears once in BENCHMARK.json, so each has a copy of the mix under a
    name of its own).  Files and appended entries only.  The COPY's own
    control, fault and file tests run ONCE over all of it."""
    tmp_path = tmp_path_factory.mktemp("clockless")
    before = _copy(tmp_path)
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    nb = tmp_path / "benchmark"
    served = [w["name"] for w in b["workloads"] if json.load(open(
        nb / "traffic" / (w["traffic"] + ".json")))["kind"] == "open_loop"]
    cfg = json.load(open(nb / "configs" / "tf_cnndm.json"))
    assert "summary_clock" not in cfg["init"]
    cfg["deployment"]["serve"] = {
        "serve_mode": "continuous", "serve_slots": 64,
        "serve_max_queue": 4096, "serve_buckets": "100,200,400"}
    cfg["rehearse"] = {"hparams": {"hidden_dim": 24, "ffn_dim": 48},
                       "deployment": {"serve": {"serve_slots": 3}}}
    mix = json.load(open(nb / "traffic" / "news_open_loop.json"))
    del mix["summary"]

    def add_config(name, **stated):
        c = dict(cfg, name=name, **stated)
        json.dump(c, open(nb / "configs" / f"{name}.json", "w"))
        b["configs"].append({"name": name, "source": c["source"],
                             "file": f"benchmark/configs/{name}.json",
                             "reduced": [], "why": "throw-away"})

    def add_cell(cell, config, traffic, sample, limits):
        json.dump(mix, open(nb / "traffic" / f"{traffic}.json", "w"))
        json.dump({"name": cell, "check": {"sample": sample},
                   "limits": limits},
                  open(nb / "workloads" / f"{cell}.json", "w"))
        b["workloads"].append({
            "name": cell, "config": config, "traffic": traffic, "chips": 1,
            "why": "throw-away: every summary max_dec_steps"})

    add_config("tf_served")
    for cell, beam in CLOCKLESS_CELLS.items():
        add_cell(cell, "tf_served", f"news_full_length{beam}",
                 {"score": 4, "beam": beam},
                 dict(CLOCKLESS_LIMITS, **(
                     CLOCKLESS_BEAM_LIMITS if beam else {})))
    # what a configuration of bfloat16 parameters states: the stored type,
    # and which activations the model it stands for keeps in float32
    add_config("tf_bf16", param_dtype="bfloat16",
               precision="bfloat16 parameters; float32 activations",
               assumed=dict(cfg["assumed"], activations=(
                   "float32: residual stream, layer norms, softmax inputs, "
                   "copy mixture (throw-away: no published model)")))
    add_cell(BF16_CELL, "tf_bf16", "news_full_length_bf16",
             {"score": 4, "beam": 0}, BF16_LIMITS)
    # the cells report the two summary percentiles and, for the copy's
    # file tests, the served cells' per-layer metrics
    added = list(CLOCKLESS_CELLS) + [BF16_CELL]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"].extend(added)
    json.dump(b, open(tmp_path / "BENCHMARK.json", "w"))
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-rA", "benchmark/tests/test_control.py",
         "benchmark/tests/test_faults.py", "benchmark/tests/test_files.py"],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=3000,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert p.returncode == 0, p.stdout[-6000:] + p.stderr[-2000:]
    passed = [line.split()[1] for line in p.stdout.splitlines()
              if line.startswith("PASSED ")]

    def ran(test, cell):
        case = re.compile(rf"::{test}\[{re.escape(cell)}[-\]]")
        return any(case.search(t) for t in passed)

    def unchanged():
        for rel, data in before.items():
            assert open(nb / rel, "rb").read() == data, rel

    return types.SimpleNamespace(path=tmp_path, served=served, ran=ran,
                                 passed=passed, unchanged=unchanged)


def test_add_a_serving_cell_of_a_clockless_family_by_files_alone(
        clockless_copy):
    """What the next model's serving cell looks like: a cell that samples
    no search of the reference's own (`beam` 0) and holds `score_gap`
    alone (`tf_served_b0`), and a second of the same configuration and
    mix that samples one (`beam` 1) and holds the beam numbers too
    (`tf_served_b1`).  Both rehearse `correct`, and the COPY's own
    control, fault and file tests pass over them and over the accepted
    cells, with no file that was there changed."""
    copy = clockless_copy
    for cell in CLOCKLESS_CELLS:
        _rehearse(copy.path, cell)
    for cell in copy.served + list(CLOCKLESS_CELLS):
        assert copy.ran("test_serving_control_fails_the_limits", cell), \
            copy.passed
        assert copy.ran("test_a_token_altered_where_it_is_produced", cell), \
            copy.passed
    # one altered answer is held against the cells that hold a beam number
    altered = "test_an_altered_answer_fails_a_beam_number"
    assert all(copy.ran(altered, cell) for cell in copy.served), copy.passed
    for cell, beam in CLOCKLESS_CELLS.items():
        assert copy.ran(altered, cell) == bool(beam), copy.passed
    copy.unchanged()


def test_add_a_cell_of_a_bfloat16_configuration_by_files_alone(
        clockless_copy):
    """A configuration that states `param_dtype: "bfloat16"` (every
    published model the queue draws from stores bfloat16 parameters) gets
    a cell the benchmark's own tests accept: its sound reference is
    float32 over the stored leaves, its control is bfloat16 activations
    over the same leaves, and the control fails the cell's `score_gap`
    limit (PASSED in the copy, beside the accepted cells).

    The program serves the bfloat16 tree, and is NOT correct: its
    transformer takes the activations' type from the embedding rows it
    looks up (`compute_dtype` float32 casts nothing), so bfloat16
    parameters give it a bfloat16 residual stream, which is the control.
    Not worked around: the rehearsal has to read so until a family brings
    a program path that keeps float32 activations over bfloat16
    parameters (PERF.md section 7)."""
    copy = clockless_copy
    for test in ("test_serving_control_fails_the_limits",
                 "test_a_token_altered_where_it_is_produced"):
        assert copy.ran(test, BF16_CELL), copy.passed
    assert not copy.ran("test_an_altered_answer_fails_a_beam_number",
                        BF16_CELL)
    line = _rehearse(copy.path, BF16_CELL, correct=False)
    assert line["failed"] == 0
    gap, limit = line["compared"]["score_gap"]
    assert limit == BF16_LIMITS["score_gap"] and limit < gap < 0.1, line
    assert line["compared"]["compiles_in_window"] == [0, 0]
    copy.unchanged()
