"""Checkpoint lifecycle tests: retention, best-model, surgery, inspector,
TF1 import mapping."""

import json
import os

import jax
import numpy as np
import pytest

from textsummarization_on_flink_tpu.checkpoint import (
    BestModelSaver,
    Checkpointer,
    convert_to_coverage_model,
    latest_checkpoint,
    load_ckpt,
    restore_best_model,
)
from textsummarization_on_flink_tpu.checkpoint import checkpointer as ckpt_lib
from textsummarization_on_flink_tpu.checkpoint.inspect import inspect_arrays
from textsummarization_on_flink_tpu.checkpoint import tf1_import
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.models import pointer_generator as pg
from textsummarization_on_flink_tpu.train import trainer as trainer_lib


def tiny_hps(**kw):
    base = dict(hidden_dim=8, emb_dim=6, batch_size=4, max_enc_steps=10,
                max_dec_steps=5, beam_size=2, min_dec_steps=2, vocab_size=32,
                max_oov_buckets=4)
    base.update(kw)
    return HParams(**base)


@pytest.fixture()
def state():
    hps = tiny_hps()
    return trainer_lib.init_train_state(hps, hps.vocab_size, seed=3)


def test_save_restore_roundtrip(tmp_path, state):
    ck = Checkpointer(str(tmp_path), hps=tiny_hps())
    path = ck.save(state)
    assert os.path.exists(path)
    restored = ck.restore()
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestShardedRoundTrip:
    """ISSUE 8: checkpoint round-trip of a SHARDED TrainState — save
    from one mesh shape, restore onto a different one against the
    sharding registry's specs, bit-parity after gather; including the
    bf16 opt-state widen (save: npz cannot hold bf16) / narrow
    (restore_sharded re-applies --opt_state_dtype) path."""

    def _mesh_hps(self, **kw):
        # vocab 32 divides tp=2/4; batch 4 divides dp=2/4
        return tiny_hps(**kw)

    @pytest.mark.parametrize("save_mesh,load_mesh",
                             [((4, 2), (2, 2)), ((2, 2), (4, 1))])
    def test_save_sharded_restore_other_mesh_bit_parity(
            self, tmp_path, save_mesh, load_mesh):
        from textsummarization_on_flink_tpu.parallel import mesh as mesh_lib

        hps = self._mesh_hps(dp=save_mesh[0], tp=save_mesh[1])
        state = trainer_lib.init_train_state(hps, hps.vocab_size, seed=3)
        plan_a = mesh_lib.make_mesh(hps)
        sharded = mesh_lib.shard_train_state(plan_a, state)
        ck = Checkpointer(str(tmp_path), hps=hps)
        ck.save(sharded)

        hps_b = self._mesh_hps(dp=load_mesh[0], tp=load_mesh[1])
        plan_b = mesh_lib.make_mesh(hps_b)
        restored = ck.restore_sharded(plan_b)
        assert restored is not None
        # placed against the registry specs on the NEW mesh
        emb = restored.params["embedding"]
        assert emb.sharding.spec == plan_b.registry.param_specs(
            restored.params)["embedding"]
        assert len(emb.sharding.device_set) == load_mesh[0] * load_mesh[1]
        # bit parity with the original host state after gather
        for a, b in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves(jax.device_get(restored))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_bf16_opt_state_widen_narrow_round_trip(self, tmp_path):
        """bf16 accumulators widen losslessly to f32 in the npz and
        re-narrow on restore_sharded — bitwise-identical bf16 payloads
        across a mesh-shape change."""
        import jax.numpy as jnp

        from textsummarization_on_flink_tpu.parallel import mesh as mesh_lib

        hps = self._mesh_hps(dp=4, tp=2, opt_state_dtype="bfloat16")
        state = trainer_lib.init_train_state(hps, hps.vocab_size, seed=5)
        acc0 = jax.tree_util.tree_leaves(state.opt_state.accumulators)
        assert all(x.dtype == jnp.bfloat16 for x in acc0)
        plan_a = mesh_lib.make_mesh(hps)
        ck = Checkpointer(str(tmp_path), hps=hps)
        ck.save(mesh_lib.shard_train_state(plan_a, state))
        # the npz holds f32 (npz degrades bf16 to void otherwise)
        flat = ckpt_lib.load_arrays(latest_checkpoint(str(tmp_path)))
        acc_keys = [k for k in flat if k.startswith("opt_state/")]
        assert acc_keys and all(flat[k].dtype == np.float32
                                for k in acc_keys)
        plan_b = mesh_lib.make_mesh(hps.replace(dp=2, tp=2))
        restored = ck.restore_sharded(plan_b)
        for a, b in zip(acc0, jax.tree_util.tree_leaves(
                jax.device_get(restored.opt_state.accumulators))):
            assert b.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))

    def test_restore_sharded_empty_dir_returns_none(self, tmp_path):
        from textsummarization_on_flink_tpu.parallel import mesh as mesh_lib

        hps = self._mesh_hps(dp=2, tp=1)
        ck = Checkpointer(str(tmp_path), hps=hps)
        assert ck.restore_sharded(mesh_lib.make_mesh(hps)) is None


def test_hparams_sidecar_written_on_first_save_not_construction(
        tmp_path, state):
    """The constructor is filesystem-only (consulting is_chief there
    would force JAX backend init, and with it take the chip); the
    provenance sidecar lands with the first save."""
    ck = Checkpointer(str(tmp_path), hps=tiny_hps())
    sidecar = os.path.join(str(tmp_path), "hparams.json")
    assert not os.path.exists(sidecar)
    ck.save(state)
    assert os.path.exists(sidecar)
    with open(sidecar, encoding="utf-8") as f:
        assert json.load(f)["hidden_dim"] == tiny_hps().hidden_dim


def test_retention_keeps_three(tmp_path, state):
    ck = Checkpointer(str(tmp_path), max_to_keep=3)
    for step in range(5):
        s = state._replace(step=np.asarray(step, np.int32))
        ck.save(s)
    files = sorted(f for f in os.listdir(tmp_path) if f.endswith(".npz"))
    assert files == ["model.ckpt-2.npz", "model.ckpt-3.npz", "model.ckpt-4.npz"]
    assert latest_checkpoint(str(tmp_path)).endswith("model.ckpt-4.npz")


def test_load_ckpt_raises_when_empty(tmp_path):
    with pytest.raises(FileNotFoundError):
        load_ckpt(str(tmp_path), max_retries=1, retry_secs=0.01)


def test_load_ckpt_finds_latest(tmp_path, state):
    ck = Checkpointer(str(tmp_path))
    ck.save(state._replace(step=np.asarray(7, np.int32)))
    path, flat = load_ckpt(str(tmp_path), max_retries=0)
    assert path.endswith("model.ckpt-7.npz")
    assert "params/embedding" in flat


def test_best_model_saver_keeps_one(tmp_path, state):
    bs = BestModelSaver(str(tmp_path))
    bs(state.params, 3.0, 10)
    bs(state.params, 2.5, 20)
    files = [f for f in os.listdir(tmp_path) if f.startswith("bestmodel")]
    # one checkpoint + its checksum manifest sidecar (RESILIENCE.md)
    assert sorted(files) == ["bestmodel-20.npz", "bestmodel-20.npz.sum"]
    assert latest_checkpoint(
        str(tmp_path), ckpt_lib.BEST_INDEX_FILE).endswith("bestmodel-20.npz")


def test_convert_to_coverage_model(tmp_path, state):
    hps = tiny_hps()
    ck = Checkpointer(str(tmp_path))
    ck.save(state)
    out = convert_to_coverage_model(str(tmp_path), hps, seed=9)
    assert out.endswith("_cov_init.npz")
    new_state = ckpt_lib.arrays_to_state(ckpt_lib.load_arrays(out))
    old_wc = np.asarray(state.params["decoder"]["attention"]["w_c"])
    new_wc = np.asarray(new_state.params["decoder"]["attention"]["w_c"])
    assert not np.allclose(old_wc, new_wc)  # freshly initialized
    np.testing.assert_array_equal(
        np.asarray(new_state.params["embedding"]),
        np.asarray(state.params["embedding"]))
    # fresh accumulator for w_c only
    np.testing.assert_allclose(
        np.asarray(new_state.opt_state.accumulators["decoder"]["attention"]["w_c"]),
        hps.adagrad_init_acc)
    # the index now points at the converted checkpoint
    assert latest_checkpoint(str(tmp_path)) == out


def test_restore_best_model(tmp_path, state):
    hps = tiny_hps()
    eval_dir = str(tmp_path / "eval")
    train_dir = str(tmp_path / "train")
    os.makedirs(train_dir)
    BestModelSaver(eval_dir)(state.params, 1.0, 42)
    out = restore_best_model(eval_dir, train_dir, hps)
    rs = ckpt_lib.arrays_to_state(ckpt_lib.load_arrays(out))
    np.testing.assert_array_equal(np.asarray(rs.params["embedding"]),
                                  np.asarray(state.params["embedding"]))
    np.testing.assert_allclose(
        np.asarray(rs.opt_state.accumulators["embedding"]),
        hps.adagrad_init_acc)
    assert int(rs.step) == 42


def test_inspect_arrays_reports_nans():
    flat = {"good": np.ones(3), "half": np.array([1.0, np.nan]),
            "bad": np.full(2, np.inf), "ints": np.arange(3)}
    rep = inspect_arrays(flat)
    assert rep["finite"] == ["good", "ints"]
    assert rep["some_infnan"] == ["half"]
    assert rep["all_infnan"] == ["bad"]


# ---- TF1 import ----

def _fake_tf1_vars(hps, vsize, include_coverage=True):
    H, E, D = hps.hidden_dim, hps.emb_dim, 2 * hps.hidden_dim
    rng = np.random.RandomState(0)
    dec = tf1_import._DEC
    shapes = {
        "seq2seq/embedding/embedding": (vsize, E),
        "seq2seq/encoder/bidirectional_rnn/fw/lstm_cell/kernel": (E + H, 4 * H),
        "seq2seq/encoder/bidirectional_rnn/fw/lstm_cell/bias": (4 * H,),
        "seq2seq/encoder/bidirectional_rnn/bw/lstm_cell/kernel": (E + H, 4 * H),
        "seq2seq/encoder/bidirectional_rnn/bw/lstm_cell/bias": (4 * H,),
        "seq2seq/reduce_final_st/w_reduce_c": (D, H),
        "seq2seq/reduce_final_st/w_reduce_h": (D, H),
        "seq2seq/reduce_final_st/bias_reduce_c": (H,),
        "seq2seq/reduce_final_st/bias_reduce_h": (H,),
        f"{dec}/W_h": (1, 1, D, D),
        f"{dec}/v": (D,),
        f"{dec}/Attention/Linear/Matrix": (D, D),
        f"{dec}/Attention/Linear/Bias": (D,),
        f"{dec}/Linear/Matrix": (E + D, E),
        f"{dec}/Linear/Bias": (E,),
        f"{dec}/lstm_cell/kernel": (E + H, 4 * H),
        f"{dec}/lstm_cell/bias": (4 * H,),
        f"{dec}/calculate_pgen/Linear/Matrix": (D + H + H + E, 1),
        f"{dec}/calculate_pgen/Linear/Bias": (1,),
        f"{dec}/AttnOutputProjection/Linear/Matrix": (H + D, H),
        f"{dec}/AttnOutputProjection/Linear/Bias": (H,),
        "seq2seq/output_projection/w": (H, vsize),
        "seq2seq/output_projection/v": (vsize,),
        "global_step": (),
    }
    if include_coverage:
        shapes[f"{dec}/coverage/w_c"] = (1, 1, 1, D)
    out = {n: np.asarray(rng.randn(*s), np.float32) for n, s in shapes.items()}
    out["seq2seq/embedding/embedding/Adagrad"] = np.ones((vsize, E), np.float32)
    return out


def test_tf1_import_shapes_match_init(state):
    hps = tiny_hps()
    imported = tf1_import.import_tf1_arrays(
        _fake_tf1_vars(hps, hps.vocab_size))
    ours = state.params
    imp_flat = ckpt_lib._flatten(imported)
    our_flat = ckpt_lib._flatten(ours)
    assert set(imp_flat) == set(our_flat)
    for k in our_flat:
        assert imp_flat[k].shape == our_flat[k].shape, k


def test_tf1_import_runs_forward(state):
    hps = tiny_hps(coverage=True)
    params = tf1_import.import_tf1_arrays(_fake_tf1_vars(hps, hps.vocab_size))
    from __graft_entry__ import _example_arrays
    arrays = _example_arrays(hps, np.random.RandomState(1))
    out = pg.forward_train(params, hps, arrays)
    assert np.isfinite(float(out.total_loss))


def test_tf1_import_missing_coverage_ok(state):
    hps = tiny_hps()
    params = tf1_import.import_tf1_arrays(
        _fake_tf1_vars(hps, hps.vocab_size, include_coverage=False))
    assert "w_c" not in params["decoder"]["attention"]


def test_tf1_import_unmapped_raises():
    with pytest.raises(KeyError):
        tf1_import.import_tf1_arrays({"bogus/var": np.zeros(2)})
