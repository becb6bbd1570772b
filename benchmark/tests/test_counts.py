"""The FLOP count functions agree with bench.py's at reference shapes
(they are copies; bench.py is the original, to be deleted later)."""
import json
import os

import pytest

from harness import counts, reference
from tests.tiny import BENCH


@pytest.mark.parametrize("name,fn", [
    ("pg_see2017", "train_flops_per_step"),
    ("tf_cnndm", "transformer_flops_per_step")])
def test_train_flops_equal_bench_py(name, fn):
    import bench
    from textsummarization_on_flink_tpu.config import HParams

    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    dep = cfg["deployment"]["train"]
    hps = HParams(batch_size=dep["batch_size"], **cfg["hparams"])
    mine = counts.train_step(reference.family(cfg["family"]),
                             cfg["hparams"], dep)
    assert mine["flops"] == pytest.approx(getattr(bench, fn)(hps), rel=1e-12)
    assert mine["bytes"] > 6 * 4 * 20e6


def test_parameter_counts_and_decode_counts():
    with open(os.path.join(BENCH, "configs", "pg_see2017.json")) as f:
        pg = json.load(f)
    with open(os.path.join(BENCH, "configs", "tf_cnndm.json")) as f:
        tf = json.load(f)
    fams = [reference.family(c["family"]) for c in (pg, tf)]
    assert 21e6 < counts.n_params(fams[0], pg["hparams"]) < 22e6
    assert 50e6 < counts.n_params(fams[1], tf["hparams"]) < 60e6
    for fam, cfg in zip(fams, (pg, tf)):
        dep = dict(chunk=25, slots=64)
        one = counts.slot_chunk(fam, cfg["hparams"], dep, 1.0, 400.0)
        two = counts.slot_chunk(fam, cfg["hparams"], dep, 2.0, 400.0)
        assert two["flops"] == pytest.approx(2 * one["flops"])
        assert one["bytes"] < two["bytes"] < 2 * one["bytes"]
        short = counts.prefill(fam, cfg["hparams"], dep, 100.0)
        long = counts.prefill(fam, cfg["hparams"], dep, 400.0)
        assert 0 < short["flops"] < long["flops"]
