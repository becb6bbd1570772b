"""Byte-diet lever tests (ISSUE 5; PERF.md 'Byte diet').

* Streaming chunked vocab loss (--loss_chunk): token-exact forward and
  grad-parity (<1e-6 rel on f32 CPU) vs the materialized path, for BOTH
  model families, pointer and baseline-CE losses, with a chunk size that
  does NOT divide T_dec (the padded-tail path).
* bf16 Adagrad accumulator (--opt_state_dtype=bfloat16): storage dtype,
  f32-update-math single-step closeness, N-step drift tolerance vs f32,
  and checkpoint round trip (npz cannot hold bf16 — widened on save,
  re-narrowed on resume).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.models import pointer_generator as pg
from textsummarization_on_flink_tpu.models import transformer as tfm
from textsummarization_on_flink_tpu.ops import losses as loss_ops
from textsummarization_on_flink_tpu.train import optim
from textsummarization_on_flink_tpu.train import trainer as trainer_lib
from __graft_entry__ import _example_arrays

CHUNK = 2  # deliberately does not divide max_dec_steps=5 below


def family_hps(family: str, **kw) -> HParams:
    base = dict(batch_size=2, max_enc_steps=7, max_dec_steps=5,
                min_dec_steps=1, hidden_dim=8, emb_dim=8, max_oov_buckets=3,
                vocab_size=32, beam_size=2, model_family=family)
    if family == "transformer":
        base.update(num_heads=2, enc_layers=2, dec_layers=2)
    else:
        base.update(coverage=True)
    base.update(kw)
    return HParams(**base)


def _grad_parity(loss_fn, params, hps_a, hps_b, rel=1e-6, atol=0.0,
                 bf16_leaves=()):
    """Leaf-wise gradient agreement: rel of the leaf's scale plus atol.
    A leaf whose path is in `bf16_leaves` has its gradient ROUNDED to
    bf16 on the way out, so each of ITS elements may in addition land
    one bf16 ulp (2^-7 of the element at most) away."""
    ga = jax.grad(loss_fn)(params, hps_a)
    gb = jax.grad(loss_fn)(params, hps_b)
    seen = set()
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ga)[0],
                            jax.tree_util.tree_leaves(gb)):
        a, b = np.asarray(a), np.asarray(b)
        scale = np.max(np.abs(a)) + 1e-12
        allowed = rel * scale + atol
        name = jax.tree_util.keystr(path)
        if name in bf16_leaves:
            seen.add(name)
            allowed = allowed + 2.0 ** -7 * np.abs(a)
        assert np.all(np.abs(a - b) <= allowed), name
    assert seen == set(bf16_leaves)


# the leaves whose gradient leaves the vocabulary projection's bf16
# matmul already rounded to bf16 (compute_dtype="bfloat16")
BF16_ROUNDED_GRADS = {
    "pointer_generator": ("['output_projection']['w']",),
    "transformer": ("['embedding']",),  # tied: it IS the projection
}


class TestStreamingLossParity:
    @pytest.mark.parametrize("family", ["pointer_generator", "transformer"])
    @pytest.mark.parametrize("pointer_gen", [True, False])
    def test_forward_and_grad_parity(self, family, pointer_gen):
        """--loss_chunk vs materialized: same loss (token-exact math; the
        final scalar mean may reassociate, hence rel 1e-6) and <1e-6 rel
        gradients, including the chunk-does-not-divide-T padded tail."""
        hps = family_hps(family, pointer_gen=pointer_gen)
        mod = tfm if family == "transformer" else pg
        params = mod.init_params(hps, hps.vocab_size, jax.random.PRNGKey(0))
        arrays = _example_arrays(hps, np.random.RandomState(0))

        def loss(p, h):
            return mod.forward_train(p, h, arrays).total_loss

        l_mat = float(loss(params, hps))
        l_chunk = float(loss(params, hps.replace(loss_chunk=CHUNK)))
        assert l_chunk == pytest.approx(l_mat, rel=1e-6)
        _grad_parity(loss, params, hps, hps.replace(loss_chunk=CHUNK))

    @pytest.mark.parametrize("family", ["pointer_generator", "transformer"])
    def test_bf16_compute_dtype_parity(self, family):
        """The chunked path must project through the SAME dtype-aware
        matmul as the materialized one (losses.project_scores), so bf16
        mode stays chunk-invariant too."""
        hps = family_hps(family, compute_dtype="bfloat16")
        mod = tfm if family == "transformer" else pg
        params = mod.init_params(hps, hps.vocab_size, jax.random.PRNGKey(1))
        arrays = _example_arrays(hps, np.random.RandomState(1))

        def loss(p, h):
            return mod.forward_train(p, h, arrays).total_loss

        assert float(loss(params, hps.replace(loss_chunk=CHUNK))) == \
            pytest.approx(float(loss(params, hps)), rel=1e-5)
        # looser than the f32 pin: bf16-rounded operands make the chunked
        # dw accumulation order visible at ~1e-4 rel, and near-zero
        # leaves (max ~1e-6) need an atol floor.  Only the
        # vocab-projection weight's gradient is itself rounded to bf16,
        # so a different accumulation order lands one bf16 ulp away
        # (2.1e-8 on pg's w at magnitude 5.6e-6, 1.6e-3 on the
        # transformer's tied embedding at 0.27): that leaf alone gets
        # the per-element ulp, every other leaf keeps rel/atol as they
        # were
        _grad_parity(loss, params, hps, hps.replace(loss_chunk=CHUNK),
                     rel=1e-4, atol=1e-8,
                     bf16_leaves=BF16_ROUNDED_GRADS[family])

    def test_chunk_larger_than_t_and_chunk_one(self):
        """Degenerate chunk sizes: 1 (maximum streaming) and > T_dec
        (clamped — single chunk, still the streaming code path)."""
        hps = family_hps("pointer_generator")
        params = pg.init_params(hps, hps.vocab_size, jax.random.PRNGKey(2))
        arrays = _example_arrays(hps, np.random.RandomState(2))

        def loss(p, h):
            return pg.forward_train(p, h, arrays).total_loss

        base = float(loss(params, hps))
        for chunk in (1, 999):
            assert float(loss(params, hps.replace(loss_chunk=chunk))) == \
                pytest.approx(base, rel=1e-6)

    def test_streaming_gold_probs_token_exact_unit(self):
        """Direct unit parity: streaming_gold_probs equals the
        materialized gold_mixture_prob_from_scores token for token."""
        rng = np.random.RandomState(3)
        T, B, H, V, Te = 5, 3, 4, 11, 6
        outputs = jnp.asarray(rng.randn(T, B, H), jnp.float32)
        attn = jnp.asarray(rng.rand(T, B, Te), jnp.float32)
        p_gens = jnp.asarray(rng.rand(T, B), jnp.float32)
        targets = jnp.asarray(rng.randint(0, V + 2, (T, B)))
        ext = jnp.asarray(rng.randint(0, V + 2, (B, Te)))
        w = jnp.asarray(rng.randn(H, V), jnp.float32)
        v = jnp.asarray(rng.randn(V), jnp.float32)
        want = loss_ops.gold_mixture_prob_from_scores(
            outputs @ w + v, attn, p_gens, targets, ext)
        for chunk in (1, 2, 5):
            got = loss_ops.streaming_gold_probs(
                outputs, attn, p_gens, targets, ext, w, v, chunk=chunk)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-7, atol=0)

    def test_no_materialized_scores_in_backward(self):
        """The claim itself: peak temp memory of grad(streaming loss)
        must stay far below one [T, B, V] scores tensor at a scale where
        that tensor dominates, while the materialized path holds ~2x of
        it (value + residual)."""
        T, B, H, V = 64, 4, 16, 2048
        rng = np.random.RandomState(4)
        outputs = jnp.asarray(rng.randn(T, B, H), jnp.float32)
        targets = jnp.asarray(rng.randint(0, V, (T, B)))
        mask = jnp.ones((T, B), jnp.float32)
        w = jnp.asarray(rng.randn(H, V) * 0.02, jnp.float32)
        v = jnp.zeros((V,), jnp.float32)

        def mat_loss(o, w, v):
            scores = o @ w + v
            log_probs = jax.nn.log_softmax(scores, axis=-1)
            nll = -jnp.take_along_axis(
                log_probs, targets[..., None], axis=-1)[..., 0]
            return jnp.sum(nll * mask) / jnp.sum(mask)

        def chunk_loss(o, w, v):
            return loss_ops.streaming_softmax_cross_entropy(
                o, targets, mask, w, v, chunk=8)

        def temp_of(fn):
            c = jax.jit(jax.grad(fn, argnums=(0, 1, 2))).lower(
                outputs, w, v).compile()
            return c.memory_analysis().temp_size_in_bytes

        scores_bytes = T * B * V * 4
        assert temp_of(mat_loss) > 1.5 * scores_bytes
        # four [chunk, B, V] buffers live at once under the installed
        # XLA:CPU: 0.502 of the scores tensor (the bound was 0.5 when an
        # older XLA kept it just under), held here with a margin of one
        # more small buffer, not of a fifth [chunk, B, V] (0.625)
        assert temp_of(chunk_loss) < 0.52 * scores_bytes


class TestBf16OptState:
    def test_init_and_update_dtypes(self):
        hps = family_hps("pointer_generator",
                         opt_state_dtype="bfloat16")
        state = trainer_lib.init_train_state(hps, hps.vocab_size, seed=0)
        for leaf in jax.tree_util.tree_leaves(state.opt_state.accumulators):
            assert leaf.dtype == jnp.bfloat16
        # params stay f32 masters
        for leaf in jax.tree_util.tree_leaves(state.params):
            assert leaf.dtype == jnp.float32
        step = jax.jit(trainer_lib.make_train_step(hps))
        arrays = _example_arrays(hps, np.random.RandomState(0))
        new_state, metrics = step(state, arrays)
        assert np.isfinite(float(metrics.loss))
        for leaf in jax.tree_util.tree_leaves(
                new_state.opt_state.accumulators):
            assert leaf.dtype == jnp.bfloat16
        for leaf in jax.tree_util.tree_leaves(new_state.params):
            assert leaf.dtype == jnp.float32

    def test_f32_path_unchanged_bit_for_bit(self):
        """The dtype-aware update must be a no-op for f32 accumulators:
        widen/narrow casts vanish and the historical formula applies."""
        params = {"w": jnp.asarray([[0.5, -0.25], [1.0, 2.0]], jnp.float32)}
        grads = {"w": jnp.asarray([[0.1, -0.2], [0.3, 0.4]], jnp.float32)}
        state = optim.adagrad_init(params, 0.1)
        new_params, new_state = optim.adagrad_update(grads, state, params,
                                                     lr=0.15)
        acc = 0.1 + np.asarray(grads["w"]) ** 2
        want = np.asarray(params["w"]) - 0.15 * np.asarray(grads["w"]) \
            / np.sqrt(acc)
        np.testing.assert_allclose(np.asarray(new_params["w"]), want,
                                   rtol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(new_state.accumulators["w"], np.float32), acc)

    def test_single_step_update_math_runs_in_f32(self):
        """One step from a FRESH bf16 accumulator: the widen->g^2->rsqrt
        chain runs in f32, so the param update differs from the pure-f32
        update only by the bf16 rounding of the INITIAL accumulator
        value (0.1 rounds to ~0.100098 in bf16: rel ~1e-3), never by
        bf16 arithmetic inside the step."""
        hps = family_hps("pointer_generator")
        state32 = trainer_lib.init_train_state(hps, hps.vocab_size, seed=0)
        state16 = trainer_lib.init_train_state(
            hps.replace(opt_state_dtype="bfloat16"), hps.vocab_size, seed=0)
        arrays = _example_arrays(hps, np.random.RandomState(0))
        step32 = jax.jit(trainer_lib.make_train_step(hps))
        step16 = jax.jit(trainer_lib.make_train_step(
            hps.replace(opt_state_dtype="bfloat16")))
        new32, m32 = step32(state32, arrays)
        new16, m16 = step16(state16, arrays)
        assert float(m16.loss) == pytest.approx(float(m32.loss), rel=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(new32.params),
                        jax.tree_util.tree_leaves(new16.params)):
            a, b = np.asarray(a), np.asarray(b)
            scale = np.max(np.abs(a)) + 1e-12
            assert np.max(np.abs(a - b)) / scale < 5e-3

    # (N steps, param-drift bound, final-loss rel bound), calibrated
    # 2026-08-02 with 2-3x headroom over measurement.  The transformer's
    # envelope is short and loose by design: its Adagrad dynamics at
    # this scale are chaotic — ANY ~1e-3 perturbation (the bf16 rounding
    # of the 0.1 initial accumulator; equally a scan-unroll change)
    # compounds to O(1) parameter divergence by step ~20 while the LOSS
    # trajectory stays equivalent, so a long tight param pin would test
    # dynamics sensitivity, not the lever.  Measured: pg drift 4.6e-3 at
    # N=30; transformer drift 0.129 at N=10.
    _DRIFT = {"pointer_generator": (30, 2e-2, 1e-2),
              "transformer": (10, 3e-1, 2e-2)}

    @pytest.mark.parametrize("family", ["pointer_generator", "transformer"])
    def test_n_step_drift_vs_f32(self, family):
        """ISSUE 5 acceptance: N-step drift tolerance pinned vs f32 —
        real training with a bf16 accumulator must stay within the
        committed envelope of the f32 run and make the same learning
        progress."""
        n, drift_tol, loss_tol = self._DRIFT[family]
        hps = family_hps(family)
        hps16 = hps.replace(opt_state_dtype="bfloat16")
        arrays = _example_arrays(hps, np.random.RandomState(1))
        s32 = trainer_lib.init_train_state(hps, hps.vocab_size, seed=1)
        s16 = trainer_lib.init_train_state(hps16, hps.vocab_size, seed=1)
        step32 = jax.jit(trainer_lib.make_train_step(hps))
        step16 = jax.jit(trainer_lib.make_train_step(hps16))
        first = None
        for _ in range(n):
            s32, m32 = step32(s32, arrays)
            s16, m16 = step16(s16, arrays)
            if first is None:
                first = float(m32.loss)
        assert float(m16.loss) == pytest.approx(float(m32.loss),
                                                rel=loss_tol)
        assert float(m16.loss) < first  # still learning
        for a, b in zip(jax.tree_util.tree_leaves(s32.params),
                        jax.tree_util.tree_leaves(s16.params)):
            a, b = np.asarray(a), np.asarray(b)
            rel = np.linalg.norm(a - b) / (np.linalg.norm(a) + 1e-12)
            assert rel < drift_tol, f"{family}: param drift {rel}"

    def test_checkpoint_roundtrip_renarrows(self, tmp_path):
        """npz cannot hold bf16: the checkpointer widens accumulators to
        f32 on save, and trainer.cast_opt_state re-narrows on resume —
        the round trip must preserve values exactly (bf16 -> f32 -> bf16
        is lossless) and restore the working dtype."""
        from textsummarization_on_flink_tpu.checkpoint.checkpointer import (
            Checkpointer,
        )

        hps = family_hps("pointer_generator",
                         opt_state_dtype="bfloat16")
        state = trainer_lib.init_train_state(hps, hps.vocab_size, seed=0)
        step = jax.jit(trainer_lib.make_train_step(hps))
        arrays = _example_arrays(hps, np.random.RandomState(0))
        state, _ = step(state, arrays)  # non-trivial accumulator values
        ckpt = Checkpointer(str(tmp_path), hps=hps)
        ckpt.save(state)
        restored = ckpt.restore()
        # on-disk form is f32 (loadable by any consumer)
        for leaf in jax.tree_util.tree_leaves(
                restored.opt_state.accumulators):
            assert np.asarray(leaf).dtype == np.float32
        recast = trainer_lib.cast_opt_state(hps, restored)
        for a, b in zip(
                jax.tree_util.tree_leaves(state.opt_state.accumulators),
                jax.tree_util.tree_leaves(recast.opt_state.accumulators)):
            assert b.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))
        # the Trainer applies the same cast on construction
        trainer = trainer_lib.Trainer(hps, hps.vocab_size, batcher=None,
                                      state=restored,
                                      train_dir=str(tmp_path))
        for leaf in jax.tree_util.tree_leaves(
                trainer.state.opt_state.accumulators):
            assert leaf.dtype == jnp.bfloat16


class TestConfigValidation:
    def test_loss_chunk_and_dtypes_validate(self):
        HParams(loss_chunk=25).validate()
        HParams(opt_state_dtype="bfloat16").validate()
        HParams(grad_allreduce_dtype="bfloat16").validate()
        with pytest.raises(ValueError, match="loss_chunk"):
            HParams(loss_chunk=-1).validate()
        with pytest.raises(ValueError, match="opt_state_dtype"):
            HParams(opt_state_dtype="fp8").validate()
        with pytest.raises(ValueError, match="grad_allreduce_dtype"):
            HParams(grad_allreduce_dtype="fp8").validate()
        # tp now composes with the bf16 wire (ISSUE 8 unification); sp
        # still rejects
        HParams(grad_allreduce_dtype="bfloat16", tp=2).validate()
        with pytest.raises(ValueError, match="sp"):
            HParams(grad_allreduce_dtype="bfloat16", sp=2,
                    max_enc_steps=400).validate()
        with pytest.raises(ValueError, match="pointer_gen"):
            HParams(grad_allreduce_dtype="bfloat16",
                    pointer_gen=False).validate()

    def test_flags_ride_the_reference_argv_surface(self):
        hps = HParams.from_argv(["--loss_chunk=25",
                                 "--opt_state_dtype=bfloat16",
                                 "--grad_allreduce_dtype=bfloat16"])
        assert hps.loss_chunk == 25
        assert hps.opt_state_dtype == "bfloat16"
        assert hps.grad_allreduce_dtype == "bfloat16"


def test_trainer_end_to_end_with_byte_diet_levers(tmp_path):
    """The full single-host Trainer loop with --loss_chunk and bf16
    optimizer state together: runs, learns, checkpoints, resumes."""
    hps = family_hps("pointer_generator", loss_chunk=2,
                     opt_state_dtype="bfloat16",
                     log_root=str(tmp_path), exp_name="bd")

    class FixedBatcher:
        def __init__(self, arrays, n):
            self.arrays, self.n = arrays, n

        def next_batch(self):
            if self.n <= 0:
                return None
            self.n -= 1
            return self  # Batch stand-in: as_arrays below

        def as_arrays(self):
            return self.arrays

    arrays = _example_arrays(hps, np.random.RandomState(0))
    trainer = trainer_lib.Trainer(hps, hps.vocab_size,
                                  FixedBatcher(arrays, 50),
                                  metrics_every=2)
    state = trainer.train(num_steps=4)
    assert int(np.asarray(state.step)) == 4
    events = os.path.join(str(tmp_path), "bd", "train", "events.jsonl")
    assert os.path.exists(events)
    for leaf in jax.tree_util.tree_leaves(state.opt_state.accumulators):
        assert leaf.dtype == jnp.bfloat16
