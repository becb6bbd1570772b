"""Drive a run (the harness's look for a chip skipped: --rehearse) with
the timed path broken underneath, and see `correct` come out false, once
for each fault a cell can have.  The cells here are one-chip cells, so
there is no exchange between chips to leave out.

The control at a size a test run can hold is in test_control.py.
"""
import json

import pytest

import run as bench_run
from tests.tiny import benchmark_with_held, cells

TRAIN = [c for c, _ in cells("train_job") + cells("train_job", held=True)]
SERVE = [c for c, _ in cells("open_loop") + cells("open_loop", held=True)]


@pytest.fixture(autouse=True)
def _held_cells_too(monkeypatch, tmp_path):
    monkeypatch.setattr(bench_run, "BENCHMARK_JSON",
                        benchmark_with_held(tmp_path))


def _rehearse(capsys, cell, seed=6):
    rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", "2", "--trace", "0", "--rehearse", "1"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", TRAIN)
def test_a_step_that_returns_its_state_unchanged(monkeypatch, capsys, cell):
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib

    real = trainer_lib.make_train_step

    def broken(hps, grad_fn=None):
        step = real(hps, grad_fn)

        def train_step(state, arrays):
            new_state, metrics = step(state, arrays)
            return state._replace(step=new_state.step), metrics
        return train_step

    monkeypatch.setattr(trainer_lib, "make_train_step", broken)
    line = _rehearse(capsys, cell)
    assert line["correct"] is False
    value, limit = line["compared"]["update_norm_gap"]
    assert value > limit and value == pytest.approx(1.0, abs=1e-3)


@pytest.mark.parametrize("cell", TRAIN)
def test_half_of_the_batch_left_out(monkeypatch, capsys, cell):
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib

    real = trainer_lib.make_grad_fn

    def broken(hps):
        grad_fn = real(hps)

        def half(params, arrays):
            n = arrays["enc_batch"].shape[0] // 2
            return grad_fn(params, {k: v[:n] for k, v in arrays.items()})
        return half

    monkeypatch.setattr(trainer_lib, "make_grad_fn", broken)
    line = _rehearse(capsys, cell)
    assert line["correct"] is False
    failed = [k for k, (v, lim) in line["compared"].items() if v > lim]
    assert failed


@pytest.mark.parametrize("cell", SERVE)
def test_a_token_altered_where_it_is_produced(monkeypatch, capsys, cell):
    import importlib

    import jax.numpy as jnp

    _, _, cfg, _, _ = bench_run.load_cell(cell)
    model = importlib.import_module(
        "textsummarization_on_flink_tpu.models."
        + cfg["hparams"]["model_family"])
    real = model.beam_adapter_masked

    def broken(hps):
        init, step = real(hps)

        def bad_step(*args, **kw):
            out = step(*args, **kw)
            ids = out.topk_ids
            # the best candidate's token is swapped for the second's;
            # its score stays
            ids = ids.at[:, 0].set(out.topk_ids[:, 1]).at[:, 1].set(
                out.topk_ids[:, 0])
            return out._replace(topk_ids=jnp.asarray(ids))
        return init, bad_step

    monkeypatch.setattr(model, "beam_adapter_masked", broken)
    line = _rehearse(capsys, cell)
    assert line["correct"] is False
    # every number the cell holds catches it: the score of the served
    # tokens always, the beam numbers where the cell samples a search
    held = set(line["compared"]) - {"compiles_in_window"}
    assert "score_gap" in held
    for number in sorted(held):
        value, limit = line["compared"][number]
        assert value > limit, number
