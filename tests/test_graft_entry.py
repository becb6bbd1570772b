"""Driver-hook smoke tests: entry() traces, dryrun_multichip executes."""

import jax
import pytest

import __graft_entry__ as ge


def test_entry_traces():
    fn, args = ge.entry()
    # Tracing (abstract evaluation) validates shapes/dtypes without paying
    # the full XLA compile; the driver does the real compile check.
    lowered = jax.jit(fn).lower(*args)
    assert lowered is not None


@pytest.mark.slow
def test_dryrun_multichip_8(monkeypatch):
    # the exact path the driver takes: scrubbed-env subprocess re-exec
    monkeypatch.delenv("TS_DRYRUN_INPROC", raising=False)
    ge.dryrun_multichip(8)


@pytest.mark.slow
def test_dryrun_multichip_1(monkeypatch):
    # in-process body (the conftest already pins the virtual CPU mesh)
    monkeypatch.setenv("TS_DRYRUN_INPROC", "1")
    ge.dryrun_multichip(1)


def test_scrubbed_env_pins_cpu_mesh(monkeypatch):
    monkeypatch.setenv("PYTHONPATH", "/other/path")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=2 --foo")
    env = ge._scrubbed_cpu_env(8)
    assert "/other/path" in env["PYTHONPATH"]
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    assert "device_count=2" not in env["XLA_FLAGS"]
    assert "--foo" in env["XLA_FLAGS"]


def test_factor_mesh():
    assert ge._factor_mesh(8) == (2, 2, 2)
    assert ge._factor_mesh(4) == (1, 2, 2)
    assert ge._factor_mesh(2) == (1, 2, 1)
    assert ge._factor_mesh(1) == (1, 1, 1)
