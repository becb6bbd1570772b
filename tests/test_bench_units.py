"""Pure-unit tests for bench.py's analytic models and config plumbing.

The MFU number the driver records is only as trustworthy as the FLOPs
model behind it; pin its basic invariants (no child processes spawned
here — the JSON contract is exercised by the driver and the verify
drives)."""

import importlib.util
import os
import sys

import pytest

spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(os.path.dirname(__file__), "..", "bench.py"))
bench = importlib.util.module_from_spec(spec)
sys.modules["bench"] = bench
spec.loader.exec_module(bench)

from textsummarization_on_flink_tpu.config import HParams  # noqa: E402


def test_pg_flops_positive_and_linear_in_batch():
    hps1 = HParams(batch_size=1)
    hps8 = HParams(batch_size=8)
    f1 = bench.train_flops_per_step(hps1)
    f8 = bench.train_flops_per_step(hps8)
    assert f1 > 0
    assert f8 == pytest.approx(8 * f1)


def test_pg_flops_dominated_by_vocab_projection():
    """At reference scale the H x 50k projection dominates (SURVEY §7.2);
    halving the vocab should cut total FLOPs by a large fraction."""
    full = bench.train_flops_per_step(HParams(batch_size=16))
    half = bench.train_flops_per_step(
        HParams(batch_size=16, vocab_size=25000))
    assert half < 0.75 * full


def test_transformer_flops_positive_linear_and_layer_scaled():
    hps = HParams(model_family="transformer", batch_size=4)
    f = bench.transformer_flops_per_step(hps)
    assert f > 0
    assert bench.transformer_flops_per_step(
        hps.replace(batch_size=8)) == pytest.approx(2 * f)
    deeper = bench.transformer_flops_per_step(
        hps.replace(enc_layers=12, dec_layers=12))
    assert deeper > f


def test_peak_flops_known_device_kinds():
    class Dev:
        def __init__(self, kind):
            self.device_kind = kind

    assert bench.peak_flops_for(Dev("TPU v4")) == pytest.approx(275e12)
    assert bench.peak_flops_for(Dev("TPU v5e")) == pytest.approx(197e12)
    assert bench.peak_flops_for(Dev("TPU v5 lite")) == pytest.approx(197e12)
    # a device that is not in the table is an error, not a default
    with pytest.raises(ValueError, match="banana9000"):
        bench.peak_flops_for(Dev("Banana9000"))


def test_input_mode_child_env_forces_cpu(monkeypatch):
    """BENCH_MODE=input is host-only: its child never takes the chip."""
    monkeypatch.setenv("BENCH_MODE", "input")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = bench._child_env()
    assert env["JAX_PLATFORMS"] == "cpu"


def test_input_bench_runs_on_host(tmp_path):
    """The input-pipeline bench end to end (tiny scale): one JSON line
    with a positive samples/s.  Runs in a subprocess like the real
    supervisor does — bench_input's Batcher threads are daemon threads
    reaped by process exit, and must not leak into this pytest
    process."""
    import json
    import subprocess

    env = dict(os.environ)
    env.update(TS_BENCH_CHILD="1", BENCH_MODE="input", BENCH_PRESET="tiny",
               BENCH_SECONDS="0.5", BENCH_BATCH="4", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "bench.py")],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "input_pipeline_samples_per_sec"
    assert rec["value"] > 0


def test_config_fingerprint_distinguishes_sweep_rows(monkeypatch):
    monkeypatch.setenv("BENCH_MODE", "train")
    for var in ("BENCH_BATCH", "BENCH_PRESET", "BENCH_FAMILY",
                "TS_PALLAS", "BENCH_PLATFORM", "BENCH_REMAT", "TS_FLASH"):
        monkeypatch.delenv(var, raising=False)
    base = bench._config_fingerprint()
    assert base == {"mode": "train", "platform": "tpu", "batch": 16,
                    "preset": "ref", "family": "pointer_generator",
                    "pallas": "off", "flash": "off", "unroll": 8,
                    "remat": False}
    # pg never reads TS_FLASH: the RESOLVED axis must not split records
    monkeypatch.setenv("TS_FLASH", "on")
    assert bench._config_fingerprint() == base
    # transformer: env forces the padded kernel -> different program
    monkeypatch.setenv("BENCH_FAMILY", "transformer")
    tf_on = bench._config_fingerprint()
    assert tf_on["flash"] == "on"
    monkeypatch.delenv("TS_FLASH")
    # auto at ref scale (T=400, hd=32 unaligned) resolves to the einsum
    # path — same program as off, so records cross-substitute correctly
    assert bench._config_fingerprint()["flash"] == "off"
    monkeypatch.delenv("BENCH_FAMILY")
    monkeypatch.setenv("BENCH_BATCH", "64")
    assert bench._config_fingerprint() != base
    # a CPU smoke record must never satisfy a TPU ask
    monkeypatch.delenv("BENCH_BATCH")
    monkeypatch.setenv("BENCH_PLATFORM", "cpu")
    assert bench._config_fingerprint() != base
    # remat is a different compiled program: its row must never stand in
    monkeypatch.delenv("BENCH_PLATFORM")
    monkeypatch.setenv("BENCH_REMAT", "1")
    assert bench._config_fingerprint() != base
    # byte-diet lever axes (ISSUE 5): different compiled programs, so
    # lever rows must never cross-substitute — and the axes appear only
    # when NON-default
    monkeypatch.delenv("BENCH_REMAT")
    monkeypatch.setenv("BENCH_LOSS_CHUNK", "25")
    chunked = bench._config_fingerprint()
    assert chunked != base and chunked["loss_chunk"] == 25
    monkeypatch.delenv("BENCH_LOSS_CHUNK")
    monkeypatch.setenv("BENCH_OPT_DTYPE", "bfloat16")
    opt = bench._config_fingerprint()
    assert opt != base and opt["opt_dtype"] == "bfloat16"
    monkeypatch.delenv("BENCH_OPT_DTYPE")
    assert bench._config_fingerprint() == base


def test_config_fingerprint_arena_axis_non_default_only(monkeypatch):
    """The ISSUE-20 paged-arena axis: an armed arena runs different
    kernels under a different admission policy, so it must split
    records — but only when armed, so banked dense serve records keep
    matching default asks."""
    monkeypatch.setenv("BENCH_MODE", "serve")
    for var in ("BENCH_SERVE_ARENA_PAGES", "BENCH_SERVE_MIX",
                "BENCH_SERVE_TIER", "BENCH_SERVE_REPLICAS",
                "BENCH_SERVE_ZIPF", "BENCH_SERVE_HIER"):
        monkeypatch.delenv(var, raising=False)
    base = bench._config_fingerprint()
    assert "arena" not in base
    monkeypatch.setenv("BENCH_SERVE_ARENA_PAGES", "24")
    armed = bench._config_fingerprint()
    assert armed != base and armed["arena"] == 24
    # 0 is the dense sentinel, not an axis value
    monkeypatch.setenv("BENCH_SERVE_ARENA_PAGES", "0")
    assert bench._config_fingerprint() == base


def test_supervisor_exits_1_without_retry_on_deterministic_failure():
    """retryable:false means a code/config regression: exit 1 with one
    error JSON line after ONE attempt — nothing stands in for a run that
    produced no result."""
    import json
    import subprocess

    env = dict(os.environ)
    env.pop("TS_BENCH_CHILD", None)
    env.update(BENCH_MODE="bogus", BENCH_ATTEMPTS="2", BENCH_TIMEOUT="60",
               BENCH_PLATFORM="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "bench.py")],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "error" in rec and rec["value"] == 0.0
    # only ONE attempt despite BENCH_ATTEMPTS=2: deterministic failures
    # must not retry
    assert "attempt 1/2" in rec["error"]


@pytest.mark.slow
def test_bytes_mode_contract_on_cpu(tmp_path):
    """BENCH_MODE=bytes end to end through the real supervisor+child at
    tiny scale: one JSON line with the lever table, reduction fields,
    and the analytic grad-allreduce bytes — the CPU-verifiable side of
    the byte-diet claims (the committed REGRESSION gate lives in
    tests/test_bytes_gate.py at the calibrated gate scale; this checks
    the bench-row contract only, so no reduction thresholds here: at
    tiny vocab the scores tensor is noise)."""
    import json
    import subprocess

    env = dict(os.environ)
    for var in ("TS_BENCH_CHILD", "BENCH_BATCH", "BENCH_PRESET",
                "BENCH_FAMILY", "BENCH_LOSS_CHUNK", "BENCH_OPT_DTYPE"):
        env.pop(var, None)
    env.update(BENCH_MODE="bytes", BENCH_PRESET="tiny", BENCH_BATCH="4",
               BENCH_LOSS_CHUNK="2", BENCH_ATTEMPTS="1",
               BENCH_TIMEOUT="300", BENCH_RUN_TAG="bytes_cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "bench.py")],
        env=env, capture_output=True, text=True, timeout=360)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "train_step_bytes_accessed"
    assert rec["value"] > 0
    assert set(rec["levers"]) == {"baseline", "loss_chunk", "opt_bf16",
                                  "combined"}
    for lever in rec["levers"].values():
        assert lever["bytes"] > 0 and lever["flops"] > 0
    assert rec["levers"]["baseline"]["reduction_vs_baseline"] == 0.0
    assert rec["grad_allreduce_bytes_bf16"] * 2 == \
        rec["grad_allreduce_bytes_f32"]
    assert rec["config_fingerprint"]["mode"] == "bytes"
    assert rec["config_fingerprint"]["platform"] == "cpu"
    assert rec["config_fingerprint"]["chunk"] == 2
    assert rec["run"] == "bytes_cpu" and rec["device_count"] >= 1


def test_preset_overrides_family(monkeypatch):
    monkeypatch.setenv("BENCH_PRESET", "tiny")
    monkeypatch.setenv("BENCH_FAMILY", "transformer")
    o = bench._preset_overrides()
    assert o["model_family"] == "transformer"
    assert o["hidden_dim"] % o["num_heads"] == 0
    # the overrides must build a valid HParams
    HParams(**o).validate()
    monkeypatch.delenv("BENCH_FAMILY")
    o2 = bench._preset_overrides()
    assert "model_family" not in o2


@pytest.mark.slow
def test_decode_child_reports_step_usage(tmp_path):
    """BENCH_MODE=decode end to end through the real supervisor+child on
    CPU at tiny scale: the record carries the loop-decision data
    (gen_steps_p50/max vs max_dec_steps — PERF.md's corrected chunked
    rule reads these) and carries a decode fingerprint."""
    import json
    import subprocess

    env = dict(os.environ)
    for var in ("TS_BENCH_CHILD", "BENCH_BATCH", "BENCH_PRESET",
                "BENCH_FAMILY", "TS_PALLAS",
                "TS_BEAM_LOOP", "BENCH_STOP_BIAS", "BENCH_DECODE_FIXTURE"):
        env.pop(var, None)
    env.update(BENCH_MODE="decode", BENCH_PRESET="tiny", BENCH_STEPS="2",
               BENCH_BATCH="2", BENCH_ATTEMPTS="1", BENCH_TIMEOUT="240",
               BENCH_PLATFORM="cpu", BENCH_RUN_TAG="decode_b4")
    proc = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__), "..",
                                      "bench.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["max_dec_steps"] >= rec["gen_steps_max"]
    assert rec["gen_steps_max"] >= rec["gen_steps_p50"] >= 1
    assert rec["config_fingerprint"]["mode"] == "decode"
    # STOP-capable params are the default: the record and fingerprint
    # both carry the params source so a worst-case random-init
    # measurement can never be mistaken for it
    assert rec["params_source"].startswith("stop_bias:")
    assert rec["config_fingerprint"]["params"] == rec["params_source"]
    # the child ran the loop kind the fingerprint names (`auto`)
    assert rec["beam_loop"] == rec["config_fingerprint"]["beam_loop"] \
        == "chunked"


def test_decode_params_spec_fixture_detection(tmp_path, monkeypatch):
    """'fixture' exactly when the family's fixture file exists (or
    BENCH_DECODE_FIXTURE points at one); ''/'0'/'none' disable; else the
    calibrated stop-bias spec with the env-overridable magnitude."""
    monkeypatch.delenv("BENCH_DECODE_FIXTURE", raising=False)
    monkeypatch.delenv("BENCH_STOP_BIAS", raising=False)
    assert bench._decode_params_spec("no_such_family") == "stop_bias:6"
    monkeypatch.setenv("BENCH_STOP_BIAS", "5.5")
    assert bench._decode_params_spec("no_such_family") == "stop_bias:5.5"
    fx = tmp_path / "fx.npz"
    fx.write_bytes(b"one fixture")
    monkeypatch.setenv("BENCH_DECODE_FIXTURE", str(fx))
    spec1 = bench._decode_params_spec("no_such_family")
    assert spec1.startswith("fixture:") and len(spec1.split(":")[1]) == 12
    # a REGENERATED fixture (different content) must change the spec so
    # banked decode rows are invalidated, not cross-substituted
    fx.write_bytes(b"another fixture, retrained")
    os.utime(fx, (1, 1))  # force a distinct (size,mtime) cache key
    spec2 = bench._decode_params_spec("no_such_family")
    assert spec2.startswith("fixture:") and spec2 != spec1
    monkeypatch.setenv("BENCH_DECODE_FIXTURE", "none")
    assert bench._decode_params_spec("no_such_family") == "stop_bias:5.5"
    # an explicitly requested fixture that is missing must fail loudly,
    # never silently degrade to stop-bias params
    monkeypatch.setenv("BENCH_DECODE_FIXTURE", str(tmp_path / "absent.npz"))
    with pytest.raises(ValueError, match="does not exist"):
        bench._decode_params_spec("no_such_family")
    # default-path auto-detection is gated to the reference preset (the
    # fixture is reference-scale; a tiny smoke run must not pick it up)
    monkeypatch.delenv("BENCH_DECODE_FIXTURE")
    monkeypatch.setenv("BENCH_PRESET", "tiny")
    assert bench._decode_params_spec(
        "no_such_family") == "stop_bias:5.5"


def test_stop_biased_bumps_only_vocab_sized_bias_vectors():
    import jax.numpy as jnp

    from textsummarization_on_flink_tpu.data.vocab import STOP_ID

    vsize = 64
    params = {"out_bias": jnp.zeros((vsize,)),
              "w": jnp.zeros((4, vsize)),  # matrix: untouched
              "other": jnp.zeros((vsize + 1,))}
    out = bench._stop_biased(params, vsize, 3.0)
    assert float(out["out_bias"][STOP_ID]) == 3.0
    assert float(jnp.sum(jnp.abs(out["out_bias"]))) == 3.0
    assert float(jnp.sum(jnp.abs(out["w"]))) == 0.0
    assert float(jnp.sum(jnp.abs(out["other"]))) == 0.0


def test_load_decode_fixture_roundtrip_and_shape_guard(tmp_path):
    import jax
    import numpy as np

    init = {"a": {"b": np.zeros((2, 3), np.float32)},
            "c": [np.ones((4,), np.float32)]}
    flat, _ = jax.tree_util.tree_flatten_with_path(init)
    path = tmp_path / "fx.npz"
    np.savez(path, **{jax.tree_util.keystr(k): v * 2 + 1
                      for k, v in flat})
    out = bench._load_decode_fixture(str(path), init)
    assert np.allclose(out["a"]["b"], 1.0) and np.allclose(out["c"][0], 3.0)
    # wrong-scale fixture fails loudly
    bad = {"a": {"b": np.zeros((2, 3), np.float32)},
           "c": [np.ones((5,), np.float32)]}
    with pytest.raises(ValueError, match="shape"):
        bench._load_decode_fixture(str(path), bad)
    # model grew a leaf the fixture lacks -> missing
    grown = dict(init, d=np.zeros((1,), np.float32))
    with pytest.raises(ValueError, match="missing"):
        bench._load_decode_fixture(str(path), grown)
    # fixture holds leaves the model no longer has (different config,
    # e.g. coverage) -> fails loudly instead of silently partial-loading
    with pytest.raises(ValueError, match="keys the model does not"):
        bench._load_decode_fixture(str(path), {"a": {"b": init["a"]["b"]}})


def test_file_digest_same_second_same_size_regen(tmp_path):
    """A regenerated fixture with the same byte size in the same mtime
    SECOND must get a fresh digest — the cache key includes
    st_mtime_ns, not the truncated-second mtime."""
    fx = tmp_path / "fixture.npz"
    fx.write_bytes(b"fixture content A")
    os.utime(fx, ns=(1_000_000_000, 5_000_000_000))
    d1 = bench._file_digest(str(fx))
    # same size, same integer second (5), different nanoseconds
    fx.write_bytes(b"fixture content B")
    os.utime(fx, ns=(1_000_000_000, 5_000_000_500))
    d2 = bench._file_digest(str(fx))
    assert d1 != d2, ("same-second same-size regen served a stale "
                      "content digest")
    # identical stat -> cache hit (no rehash needed): digest stable
    assert bench._file_digest(str(fx)) == d2
