"""Roofline-report tool tests (scripts/roofline.py): the XLA cost-model
numbers must exist, be self-consistent, and agree with bench.py's
analytic FLOPs model to within fusion/backward-counting slack — the
cross-check that keeps the MFU denominator honest."""

import importlib.util
import json
import os
import sys

import pytest

spec = importlib.util.spec_from_file_location(
    "roofline", os.path.join(os.path.dirname(__file__), "..", "scripts",
                             "roofline.py"))
roofline = importlib.util.module_from_spec(spec)
sys.modules["roofline"] = roofline
spec.loader.exec_module(roofline)


def test_tiny_config_costs_are_consistent():
    bench_mod = roofline._load_bench()
    rec = roofline.analyze("train_tiny", "v5e", bench_mod, None)
    assert rec["xla_flops"] > 0
    assert rec["bytes_accessed"] > 0
    # XLA counts every op (elementwise, softmax, full backward as
    # written); the analytic model is matmul MACs x3.  They must agree
    # to within fusion/counting slack, not orders of magnitude.
    assert 0.5 <= rec["flops_ratio_xla_over_analytic"] <= 6.0, rec
    # floors: min_step is the max of the two floors, and samples/s match
    assert rec["min_step_ms"] == max(rec["compute_floor_ms"],
                                     rec["bandwidth_floor_ms"])
    assert rec["max_samples_per_sec"] > 0
    assert rec["bound"] in ("bandwidth", "compute")


def test_byte_diet_lever_configs_resolve():
    """The lever rows (ISSUE 5) must resolve through the SAME env
    mapping the sweep uses — hps_for is the single source, so the
    roofline always describes exactly the config bench.py measures."""
    bench_mod = roofline._load_bench()
    assert roofline.hps_for("train_b16_losschunk", bench_mod).loss_chunk \
        == 25
    assert roofline.hps_for("train_b16_optbf16",
                            bench_mod).opt_state_dtype == "bfloat16"
    both = roofline.hps_for("train_b16_bytediet", bench_mod)
    assert both.loss_chunk == 25 and both.opt_state_dtype == "bfloat16"
    tfc = roofline.hps_for("train_transformer_losschunk", bench_mod)
    assert tfc.model_family == "transformer" and tfc.loss_chunk == 25
    # every lever row's declared baseline is itself a known config
    for tag, base in roofline._BYTE_DIET_BASELINES.items():
        assert tag in roofline.CONFIGS and base in roofline.CONFIGS


def test_measured_join_uses_measurements_only(tmp_path):
    path = tmp_path / "rows.jsonl"
    rows = [
        {"metric": "train_samples_per_sec", "run": "train_b16",
         "value": 600.0, "step_time_ms": 26.7,
         "captured_at": "2026-07-30T10:00:00Z"},
        {"metric": "train_samples_per_sec", "run": "train_b64",
         "value": 0.0, "error": "timed out"},
        {"metric": "train_samples_per_sec", "run": "train_b16",
         "value": 500.0, "step_time_ms": 32.0,
         "captured_at": "2026-07-30T09:00:00Z"},
    ]
    path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    m = roofline.measured_rows(str(path))
    assert set(m) == {"train_b16"}  # error rows are not measurements
    assert m["train_b16"]["step_time_ms"] == 26.7  # newest wins
    assert roofline.measured_rows(None) == {}  # no file, no join


@pytest.mark.slow
def test_cli_json_smoke(capsys):
    rc = roofline.main(["--configs", "train_tiny", "--json"])
    assert rc == 0
    out = [json.loads(l) for l in
           capsys.readouterr().out.strip().splitlines()]
    assert out and out[0]["config"] == "train_tiny"
    assert "measured_step_ms" not in out[0]


@pytest.mark.slow
def test_attribution_phases_consistent():
    """Phase attribution: forward ⊆ fwd+bwd ⊆ full step in both flops
    and bytes, and the diffs are what the table reports."""
    bench_mod = roofline._load_bench()
    hps = roofline.hps_for("train_tiny", bench_mod)
    att = roofline.attribution_of(hps)
    for k in ("flops", "bytes"):
        assert att["forward"][k] > 0
        assert att["fwd+bwd"][k] > 0
        assert att["full step"][k] > 0
        # the diffs must be exactly what the table reports
        assert att["backward (diff)"][k] == (att["fwd+bwd"][k]
                                             - att["forward"][k])
        assert att["optimizer (diff)"][k] == (att["full step"][k]
                                              - att["fwd+bwd"][k])
    # flop counts are fusion-independent, so phase monotonicity is a
    # real invariant for them; bytes-accessed is fusion-dependent
    # (roofline.py docstring) and only sanity-bounded here
    assert att["fwd+bwd"]["flops"] >= att["forward"]["flops"]
    assert att["full step"]["flops"] >= att["fwd+bwd"]["flops"]
    assert att["fwd+bwd"]["bytes"] >= 0.5 * att["forward"]["bytes"]
    # pg family: the encoder seam splits forward
    assert att["encoder fwd"]["flops"] > 0
    assert att["forward"]["flops"] >= att["encoder fwd"]["flops"]
    assert att["dec+loss fwd (diff)"]["flops"] == (
        att["forward"]["flops"] - att["encoder fwd"]["flops"])
    # bytes diffs may undershoot when fusion overlaps the standalone
    # phases (docstring); bound loosely rather than exactly
    assert att["dec+loss fwd (diff)"]["bytes"] >= \
        -0.25 * att["forward"]["bytes"]
