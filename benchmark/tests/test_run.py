"""run.py as a command: refuses to measure on the CPU, refuses a checkout
without the program, and rehearses every cell end to end at tiny shapes
with a line that carries no device metric."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from tests.tiny import BENCH

ROOT = os.path.dirname(BENCH)


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _run(args, cwd=ROOT, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "benchmark/run.py"] + args,
                          cwd=cwd, env=e, capture_output=True, text=True,
                          timeout=600)


def test_refuses_to_measure_on_cpu():
    p = _run(["--workload", _cells()[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run(["--workload", _cells()[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"], cwd=str(tmp_path), PYTHONPATH="")
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell", _cells())
def test_rehearsal_runs_the_cell_end_to_end(cell):
    p = _run(["--workload", cell, "--seed", str(2 ** 31 + 5), "--seconds",
              "2", "--trace", "1", "--rehearse", "1"],
             BENCH_RUN="ignored")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["platform"] == "cpu"
    assert "metrics" not in line and "device" not in line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is True, line
    for name, (value, limit) in line["compared"].items():
        assert value <= limit, name


def test_refuses_to_measure_without_the_native_bridge(monkeypatch, capsys):
    """The Batcher's Python fallback is another system: a build that
    fails ends the run with a code other than 0 and no result."""
    import run as bench_run
    from textsummarization_on_flink_tpu.native import build as native_build

    def no_compiler(force=False):
        raise RuntimeError("no C++ compiler found (need g++ or c++)")

    monkeypatch.setattr(native_build, "build", no_compiler)
    rc = bench_run.main(["--workload", _cells()[0], "--seed", "1",
                         "--seconds", "1", "--trace", "0", "--rehearse", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "native bridge" in out.err
