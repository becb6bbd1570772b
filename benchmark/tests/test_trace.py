"""The trace reduction on a hand-built trace with known busy share, idle
gaps and program time, and the loader on a trace recorded here."""
import time

import pytest

from harness import trace

MS = 1e6  # ns


def _planes():
    ops = [("fusion.1", 0 * MS, 10 * MS), ("while.2", 20 * MS, 30 * MS),
           ("fusion.3", 25 * MS, 5 * MS),  # nested in the while: once
           ("copy.4", 70 * MS, 10 * MS)]
    mods = [("jit_train_step(123)", 0 * MS, 10 * MS),
            ("jit_train_step(123)", 20 * MS, 30 * MS),
            ("jit_other(9)", 70 * MS, 10 * MS)]
    return {"/device:TPU:0": {trace.OPS_LINE: ops, trace.MODULES_LINE: mods},
            "host": {trace.SYNC_EVENT: [("bench_sync", 0.0, 1.0)]}}


def test_known_busy_idle_and_program_time():
    phases = [(1_000_000_000 + 10 * MS, 1_000_000_000 + 20 * MS, "a/short"),
              (1_000_000_000 + 50 * MS, 1_000_000_000 + 69 * MS, "b/long")]
    r = trace.reduce(_planes(), window_s=0.1, chips=1, phases=phases,
                     sync_epoch_ns=1_000_000_000.0)
    assert r["busy_s"] == pytest.approx(0.050)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.5)
    p = r["programs"]["jit_train_step"]
    assert p["calls"] == 2 and p["total_s"] == pytest.approx(0.040)
    assert p["mean_ms"] == pytest.approx(20.0)
    assert r["idle_gaps"][0] == ["b/long", pytest.approx(0.020)]
    assert r["idle_gaps"][1] == ["a/short", pytest.approx(0.010)]
    assert r["device_ops"][0] == ["while.2", pytest.approx(0.030)]
    m = trace.match_programs(r["programs"], "^jit_train_step$")
    assert m["calls"] == 2
    assert trace.match_programs(r["programs"], "nothing")["calls"] == 0


def test_two_devices_average_and_no_device():
    planes = _planes()
    planes["/device:TPU:1"] = {trace.OPS_LINE: [("fusion.1", 0, 30 * MS)]}
    r = trace.reduce(planes, window_s=0.1, chips=4)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((0.050 + 0.030) / 2)
    assert trace.reduce({}, 1.0)["devices"] == 0


def test_loader_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.SYNC_EVENT):
        time.sleep(0.001)
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    planes = trace.load(str(tmp_path))
    assert trace.SYNC_EVENT in planes.get("host", {})
    assert "/host:CPU" in trace.describe(str(tmp_path))
    # a CPU trace has no TPU plane: nothing to read, so no busy time
    assert trace.reduce(planes, 1.0)["devices"] == 0
