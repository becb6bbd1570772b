"""Transformer model family: training, KV-cache decode, sharding, serving.

The family must be a drop-in behind every subsystem the pointer-generator
uses: Trainer/Evaluator (same TrainOutput contract), the generic beam
search (adapter protocol), checkpointing (list-bearing pytrees), and the
(dp, tp, sp) mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.batching import Batch, SummaryExample
from textsummarization_on_flink_tpu.data.vocab import Vocab
from textsummarization_on_flink_tpu.decode import beam_search
from textsummarization_on_flink_tpu.models import get_family
from textsummarization_on_flink_tpu.models import transformer as tfm
from textsummarization_on_flink_tpu.parallel import mesh as mesh_lib
from textsummarization_on_flink_tpu.train import trainer as trainer_lib


def tiny_hps(**kw) -> HParams:
    base = dict(model_family="transformer", hidden_dim=16, emb_dim=16,
                batch_size=8, max_enc_steps=16, max_dec_steps=6, beam_size=2,
                min_dec_steps=2, vocab_size=64, max_oov_buckets=8,
                num_heads=4, enc_layers=2, dec_layers=2)
    base.update(kw)
    return HParams(**base)


def tiny_vocab(n: int = 64) -> Vocab:
    return Vocab(words=[f"w{i}" for i in range(n - 4)], max_size=n)


def make_batch(hps, vocab, seed=0):
    rng = np.random.RandomState(seed)
    exs = []
    for i in range(hps.batch_size):
        n_art = rng.randint(5, hps.max_enc_steps)
        n_abs = rng.randint(2, hps.max_dec_steps)
        art = " ".join(rng.choice([f"w{j}" for j in range(50)] + ["zzz_oov"],
                                  n_art))
        abs_ = " ".join(rng.choice([f"w{j}" for j in range(50)], n_abs))
        exs.append(SummaryExample.build(art, [abs_], vocab, hps))
    return Batch(exs, hps, vocab)


@pytest.fixture(scope="module")
def setup():
    hps = tiny_hps(coverage=True)
    vocab = tiny_vocab(hps.vocab_size)
    batch = make_batch(hps, vocab)
    state = trainer_lib.init_train_state(hps, vocab.size(), seed=7)
    return hps, vocab, batch, state


def test_get_family_dispatch():
    assert get_family("transformer") is tfm
    with pytest.raises(ValueError, match="unknown model_family"):
        get_family("perceptron")


def test_validate_rejects_bad_heads():
    with pytest.raises(ValueError, match="num_heads"):
        tiny_hps(hidden_dim=16, num_heads=3).validate()


def test_forward_train_shapes_and_finite(setup):
    hps, vocab, batch, state = setup
    out = jax.jit(lambda p, a: tfm.forward_train(p, hps, a))(
        state.params, batch.as_arrays())
    B, T_dec, T_enc = hps.batch_size, hps.max_dec_steps, hps.max_enc_steps
    assert out.attn_dists.shape == (B, T_dec, T_enc)
    assert out.p_gens.shape == (B, T_dec)
    assert np.isfinite(float(out.loss))
    assert float(out.coverage_loss) >= 0
    # copy distribution is a (masked) probability distribution per step
    sums = np.asarray(out.attn_dists).sum(-1)
    assert np.all(sums < 1.0 + 1e-4)
    pg = np.asarray(out.p_gens)
    assert np.all((pg >= 0) & (pg <= 1))


def test_training_loss_decreases(setup):
    hps, vocab, batch, state = setup
    step = jax.jit(trainer_lib.make_train_step(hps))
    losses = []
    for _ in range(8):
        state, metrics = step(state, batch.as_arrays())
        losses.append(float(metrics.loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_kv_cache_matches_teacher_forcing(setup):
    """Incremental decoding with the static KV cache must reproduce the
    teacher-forced forward pass exactly: feed the gold prefix through the
    beam-adapter step and compare per-step copy attention and p_gen."""
    hps, vocab, batch, state = setup
    hps1 = hps.replace(beam_size=1)  # K=1: one forced hypothesis
    arrays = batch.as_arrays()
    ref = tfm.forward_train(state.params, hps, arrays)

    enc_view = tfm.beam_encode(state.params, hps1, arrays)
    init_state_fn, step_fn = tfm.beam_adapter(hps1)
    b = 2  # probe one article
    enc_one = jax.tree_util.tree_map(lambda x: x[b], enc_view)
    enc_mask = arrays["enc_padding_mask"][b]
    ext_ids = arrays["enc_batch_extend_vocab"][b]
    st = init_state_fn(state.params, enc_one)
    n_steps = int(np.sum(arrays["dec_padding_mask"][b]))
    for t in range(n_steps):
        latest = arrays["dec_batch"][b, t][None]  # [K=1]
        out = step_fn(state.params, enc_one, enc_mask, ext_ids,
                      np.int32(t), latest, st)
        st = out.state
        np.testing.assert_allclose(np.asarray(out.attn_dist[0]),
                                   np.asarray(ref.attn_dists[b, t]),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(float(out.p_gen[0]),
                                   float(ref.p_gens[b, t]),
                                   rtol=1e-4, atol=1e-5)


def test_bf16_blocks_compute_in_bf16(setup):
    """bf16 activations against f32 master params must NOT silently
    promote the layer matmuls back to f32 (half the MXU's bf16 rate):
    _mha and _ffn_block cast params to the activation dtype, so their
    outputs stay bf16."""
    hps, vocab, batch, state = setup
    rng = np.random.RandomState(0)
    layer = state.params["encoder"]["layers"][0]
    x = jnp.asarray(rng.randn(2, 8, hps.hidden_dim) * 0.1, jnp.bfloat16)
    mask = jnp.ones((2, 1, 8), jnp.float32)
    out, probs = tfm._mha(hps, layer["self_attn"], x, x, mask)
    assert out.dtype == jnp.bfloat16
    assert probs.dtype == jnp.float32  # copy distribution stays f32
    assert tfm._ffn_block(layer["ffn"], x).dtype == jnp.bfloat16


def test_bf16_forward_train_close_to_f32(setup):
    hps, vocab, batch, state = setup
    arrays = batch.as_arrays()
    out32 = tfm.forward_train(state.params, hps, arrays)
    out16 = tfm.forward_train(state.params,
                              hps.replace(compute_dtype="bfloat16"), arrays)
    assert np.isfinite(float(out16.loss))
    np.testing.assert_allclose(float(out16.loss), float(out32.loss),
                               rtol=3e-2)


def test_flash_gating(monkeypatch):
    """Flash self-attention needs a TPU backend (the kernel has no
    CPU/GPU lowering): auto falls to the einsum formula off-TPU, a
    forced =on raises there; TS_FLASH=off always wins; =on engages on
    ANY shape (unaligned T/head_dim get zero-padded to the 128 grid); auto
    — the frozen default — keeps the conservative natively-aligned
    T >= 1024 rule."""
    hps_small = tiny_hps()  # hd=4 -> auto never fires
    assert not tfm._use_flash(hps_small, 400)
    hps_big = tiny_hps(hidden_dim=1024, num_heads=8)  # hd=128
    monkeypatch.setenv("TS_FLASH", "on")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert tfm._use_flash(hps_big, 1024)
    assert tfm._use_flash(hps_big, 400)  # forced: padded path handles it
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    with pytest.raises(ValueError, match="needs a TPU backend"):
        tfm._use_flash(hps_big, 1024)  # forced, but no TPU: never silent
    monkeypatch.setenv("TS_FLASH", "off")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not tfm._use_flash(hps_big, 1024)
    monkeypatch.setenv("TS_FLASH", "auto")
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not tfm._use_flash(hps_big, 1024)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert tfm._use_flash(hps_big, 1024)
    assert not tfm._use_flash(hps_big, 512)  # auto needs T >= 1024


def test_flash_branch_matches_einsum_interpret(monkeypatch):
    """Execute the ACTUAL flash branch (segment ids, head transposes,
    sm_scale) in Pallas interpret mode on CPU and compare real-row outputs
    against the einsum path."""
    from jax.experimental.pallas import tpu as pltpu

    hps = tiny_hps(hidden_dim=128, num_heads=1)  # hd=128, lane-aligned
    T, B, H = 128, 2, 128
    rng = np.random.RandomState(0)
    p = {k: jnp.asarray(rng.randn(H, H) * 0.05, jnp.float32)
         for k in ("wq", "wk", "wv", "wo")}
    x = jnp.asarray(rng.randn(B, T, H) * 0.3, jnp.float32)
    lens = np.array([T, T // 2])
    mask = jnp.asarray((np.arange(T)[None] < lens[:, None]), jnp.float32)

    monkeypatch.setenv("TS_FLASH", "off")
    ref = tfm._self_attention(hps, p, x, mask, causal=False)
    monkeypatch.setenv("TS_FLASH", "on")
    # _use_flash requires a TPU backend even when forced (the kernel has
    # no CPU lowering); interpret mode stands in for the hardware here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert tfm._use_flash(hps, T)
    with pltpu.force_tpu_interpret_mode():
        got = tfm._self_attention(hps, p, x, mask, causal=False)
        got_causal = tfm._self_attention(hps, p, x, None, causal=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    monkeypatch.setenv("TS_FLASH", "off")
    ref_causal = tfm._self_attention(hps, p, x, None, causal=True)
    real = np.asarray(mask)[:, :, None] > 0
    np.testing.assert_allclose(np.where(real, np.asarray(got), 0),
                               np.where(real, np.asarray(ref), 0),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(got_causal), np.asarray(ref_causal),
                               rtol=2e-3, atol=2e-3)


def test_flash_padded_unaligned_matches_einsum_interpret(monkeypatch):
    """TS_FLASH=on at UNALIGNED shapes (reference-class T=40, hd=32)
    zero-pads q/k/v to the 128 grid — fwd AND grad must match the
    einsum path exactly on real rows, both encoder (padding mask) and
    causal decoder.  This is the correctness gate under the
    TS_FLASH=on train step (scripts/roofline.py: the einsum path's
    materialized score tensors dominate the transformer step's
    bytes)."""
    from jax.experimental.pallas import tpu as pltpu

    hps = tiny_hps(hidden_dim=128, num_heads=4)  # hd=32: not lane-aligned
    T, B, H = 40, 2, 128
    rng = np.random.RandomState(0)
    p = {k: jnp.asarray(rng.randn(H, H) * 0.05, jnp.float32)
         for k in ("wq", "wk", "wv", "wo")}
    x = jnp.asarray(rng.randn(B, T, H) * 0.3, jnp.float32)
    lens = np.array([T, T - 13])
    mask = jnp.asarray((np.arange(T)[None] < lens[:, None]), jnp.float32)

    def f_enc(x):
        out = tfm._self_attention(hps, p, x, mask, causal=False)
        return jnp.sum((out * mask[:, :, None]) ** 2)  # mask garbage rows

    def f_dec(x):
        return jnp.sum(tfm._self_attention(hps, p, x, None, causal=True)
                       ** 2)

    monkeypatch.setenv("TS_FLASH", "off")
    refs = [f(x) for f in (f_enc, f_dec)]
    grefs = [jax.grad(f)(x) for f in (f_enc, f_dec)]
    monkeypatch.setenv("TS_FLASH", "on")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert tfm._use_flash(hps, T)
    with pltpu.force_tpu_interpret_mode():
        gots = [f(x) for f in (f_enc, f_dec)]
        ggots = [jax.grad(f)(x) for f in (f_enc, f_dec)]
    for ref, got in zip(refs, gots):
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    for gref, gflash in zip(grefs, ggots):
        err = float(jnp.max(jnp.abs(gref - gflash)))
        scale = float(jnp.max(jnp.abs(gref)))
        assert err < 1e-5 * max(scale, 1.0), (err, scale)


@pytest.mark.slow
def test_flash_grad_parity_bench_scale(monkeypatch):
    """The EXACT correctness gate bench.py's flash mode runs on hardware
    (fwd+bwd through a masked sum-of-squares loss at T=2048), executed in
    Pallas interpret mode on CPU — so only the flash *timing* needs the
    chip.  ~17s on CPU."""
    from jax.experimental.pallas import tpu as pltpu

    hps = tiny_hps(hidden_dim=128, num_heads=1)
    T, B, H = 2048, 1, 128
    rng = np.random.RandomState(0)
    p = {k: jnp.asarray(rng.randn(H, H) * 0.05, jnp.float32)
         for k in ("wq", "wk", "wv", "wo")}
    x = jnp.asarray(rng.randn(B, T, H) * 0.3, jnp.float32)
    lens = np.array([T - 256])  # real padding tail
    mask = jnp.asarray((np.arange(T)[None] < lens[:, None]), jnp.float32)

    def f(x):
        out = tfm._self_attention(hps, p, x, mask, causal=False)
        # mask the loss: padding-query rows legitimately differ between
        # the paths and must not leak gradient into the comparison
        return jnp.sum((out * mask[:, :, None]) ** 2)

    monkeypatch.setenv("TS_FLASH", "off")
    g_ref = jax.grad(f)(x)
    monkeypatch.setenv("TS_FLASH", "on")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert tfm._use_flash(hps, T)
    with pltpu.force_tpu_interpret_mode():
        g_flash = jax.grad(f)(x)
    real = np.asarray(mask)[:, :, None] > 0
    err = float(jnp.max(jnp.abs(jnp.where(real, g_ref - g_flash, 0.0))))
    scale = float(jnp.max(jnp.abs(jnp.where(real, g_ref, 0.0))))
    assert err <= 1e-2 * max(scale, 1.0), (err, scale)  # bench's gate
    assert err < 1e-6  # and far tighter in practice (observed ~3e-9)


@pytest.mark.slow
def test_remat_gradient_parity(setup):
    """--remat recomputes layer activations in backward; gradients must
    match the stored-activation path (up to FP reassociation)."""
    hps, vocab, batch, state = setup
    arrays = batch.as_arrays()
    g0 = jax.grad(
        lambda p: tfm.forward_train(p, hps, arrays).total_loss)(state.params)
    g1 = jax.grad(
        lambda p: tfm.forward_train(p, hps.replace(remat=True),
                                    arrays).total_loss)(state.params)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        a, b = np.asarray(a), np.asarray(b)
        scale = np.max(np.abs(a)) + 1e-12
        assert np.max(np.abs(a - b)) / scale < 1e-5


def test_beam_search_generic_driver(setup):
    hps, vocab, batch, state = setup
    enc_only = {k: v for k, v in batch.as_arrays().items()
                if k.startswith("enc_")}
    out = beam_search.run_beam_search(state.params, hps, enc_only)
    B, T = hps.batch_size, hps.max_dec_steps
    assert out.tokens.shape == (B, T + 1)
    assert np.all(out.tokens[:, 0] == 2)  # START
    assert np.all((out.length >= 2) & (out.length <= T + 1))
    assert np.all(np.isfinite(out.avg_log_prob))
    assert out.attn_dists.shape == (B, T, hps.max_enc_steps)


def test_checkpoint_roundtrip_with_layer_lists(setup, tmp_path):
    from textsummarization_on_flink_tpu.checkpoint import (
        checkpointer as ckpt_lib,
    )

    hps, vocab, batch, state = setup
    ck = ckpt_lib.Checkpointer(str(tmp_path), hps=hps)
    ck.save(state)
    path, flat = ckpt_lib.load_ckpt(str(tmp_path), max_retries=0)
    restored = ckpt_lib.arrays_to_state(flat)
    assert isinstance(restored.params["encoder"]["layers"], list)
    assert len(restored.params["encoder"]["layers"]) == hps.enc_layers
    ref_leaves = jax.tree_util.tree_leaves(jax.device_get(state.params))
    got_leaves = jax.tree_util.tree_leaves(restored.params)
    assert len(ref_leaves) == len(got_leaves)
    for r, g in zip(ref_leaves, got_leaves):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))


def test_coverage_conversion_rejects_transformer(setup, tmp_path):
    from textsummarization_on_flink_tpu.checkpoint import (
        checkpointer as ckpt_lib,
    )

    hps, vocab, batch, state = setup
    ckpt_lib.Checkpointer(str(tmp_path), hps=hps).save(state)
    with pytest.raises(ValueError, match="pointer_generator family only"):
        ckpt_lib.convert_to_coverage_model(str(tmp_path), hps)


@pytest.mark.parametrize("dp,tp,sp", [(8, 1, 1), (2, 2, 2)])
@pytest.mark.slow
def test_sharded_train_step_matches_single_device(setup, dp, tp, sp):
    hps, vocab, batch, state = setup
    single = jax.jit(trainer_lib.make_train_step(hps))
    ref_state, ref_metrics = single(state, batch.as_arrays())
    hps_m = hps.replace(dp=dp, tp=tp, sp=sp)
    mesh_lib.validate_divisibility(hps_m, state.params)
    plan = mesh_lib.make_mesh(hps_m)
    sharded_state = mesh_lib.shard_train_state(plan, state)
    step = mesh_lib.make_sharded_train_step(plan, donate=False)
    new_state, metrics = step(sharded_state, batch.as_arrays())
    np.testing.assert_allclose(float(metrics.loss), float(ref_metrics.loss),
                               rtol=2e-5)
    ref_leaves = jax.tree_util.tree_leaves(jax.device_get(ref_state.params))
    got_leaves = jax.tree_util.tree_leaves(jax.device_get(new_state.params))
    for r, g in zip(ref_leaves, got_leaves):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4,
                                   atol=1e-6)


def test_ring_attention_op_matches_full_attention():
    """Standalone ring op vs full masked softmax attention on a 4-device
    sp ring (padding spanning whole blocks included)."""
    from jax.sharding import Mesh
    from textsummarization_on_flink_tpu.parallel import ring_attention as ra

    devs = np.asarray(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    B, T, nh, hd = 2, 32, 2, 8
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, T, nh, hd), jnp.float32)
               for _ in range(3))
    lens = np.array([T, T // 4])  # row 1: 3 of 4 blocks are pure padding
    mask = jnp.asarray((np.arange(T)[None] < lens[:, None]), jnp.float32)
    scale = hd ** -0.5
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k) * scale
    logits = jnp.where(mask[:, None, None, :] > 0, logits, -1e30)
    p = jax.nn.softmax(logits, -1) * (mask[:, None, None, :] > 0)
    ref = jnp.einsum("bnqk,bknd->bqnd", p, v)
    out = jax.jit(ra.make_ring_attention(mesh, "sp"))(q, k, v, mask, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_ring_attention_sharded_step_matches_single_device(setup):
    """Full transformer train step with --sp_attention=ring under a
    (dp=2, sp=4) mesh == the single-device step without it."""
    hps, vocab, batch, state = setup
    single = jax.jit(trainer_lib.make_train_step(hps))
    ref_state, ref_metrics = single(state, batch.as_arrays())

    hps_m = hps.replace(dp=2, tp=1, sp=4, sp_attention="ring")
    plan = mesh_lib.make_mesh(hps_m)
    sharded_state = mesh_lib.shard_train_state(plan, state)
    step = mesh_lib.make_sharded_train_step(plan, donate=False)
    new_state, metrics = step(sharded_state, batch.as_arrays())
    np.testing.assert_allclose(float(metrics.loss), float(ref_metrics.loss),
                               rtol=2e-5)
    ref_leaves = jax.tree_util.tree_leaves(jax.device_get(ref_state.params))
    got_leaves = jax.tree_util.tree_leaves(jax.device_get(new_state.params))
    for r, g in zip(ref_leaves, got_leaves):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-4,
                                   atol=1e-6)


def test_ulysses_attention_op_matches_full_attention():
    """All-to-all SP layout vs full masked softmax attention."""
    from jax.sharding import Mesh
    from textsummarization_on_flink_tpu.parallel import ring_attention as ra

    devs = np.asarray(jax.devices()[:4]).reshape(4)
    mesh = Mesh(devs, ("sp",))
    B, T, nh, hd = 2, 32, 4, 8  # nh % sp == 0
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(B, T, nh, hd), jnp.float32)
               for _ in range(3))
    lens = np.array([T, T // 4])
    mask = jnp.asarray((np.arange(T)[None] < lens[:, None]), jnp.float32)
    scale = hd ** -0.5
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k) * scale
    logits = jnp.where(mask[:, None, None, :] > 0, logits, -1e30)
    p = jax.nn.softmax(logits, -1) * (mask[:, None, None, :] > 0)
    ref = jnp.einsum("bnqk,bknd->bqnd", p, v)
    out = jax.jit(ra.make_sp_attention(mesh, "ulysses"))(q, k, v, mask, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_ulysses_sharded_step_matches_single_device(setup):
    """Full transformer train step with --sp_attention=ulysses under a
    (dp=2, sp=4) mesh == the single-device step (num_heads=4 % sp ok)."""
    hps, vocab, batch, state = setup
    single = jax.jit(trainer_lib.make_train_step(hps))
    ref_state, ref_metrics = single(state, batch.as_arrays())
    hps_m = hps.replace(dp=2, tp=1, sp=4, sp_attention="ulysses")
    mesh_lib.validate_divisibility(hps_m, state.params)
    plan = mesh_lib.make_mesh(hps_m)
    sharded_state = mesh_lib.shard_train_state(plan, state)
    step = mesh_lib.make_sharded_train_step(plan, donate=False)
    _, metrics = step(sharded_state, batch.as_arrays())
    np.testing.assert_allclose(float(metrics.loss), float(ref_metrics.loss),
                               rtol=2e-5)


def test_ulysses_rejects_indivisible_heads(setup):
    hps, vocab, batch, state = setup
    with pytest.raises(ValueError, match="must divide num_heads"):
        mesh_lib.validate_divisibility(
            hps.replace(sp=8, max_enc_steps=16, num_heads=4,
                        sp_attention="ulysses"))


def test_ring_attention_rejects_tp(setup):
    hps, vocab, batch, state = setup
    with pytest.raises(ValueError, match="sp_attention with tp>1"):
        mesh_lib.validate_divisibility(
            hps.replace(dp=2, tp=2, sp=2, sp_attention="ring"), state.params)


def test_ring_attention_serving_matches_plain(setup):
    """Sharded beam search under --sp_attention=ring (sp>1) returns the
    same hypotheses as the single-device search without it — the serving
    path gets the mesh context too."""
    hps, vocab, batch, state = setup
    enc_only = {k: v for k, v in batch.as_arrays().items()
                if k.startswith("enc_")}
    plain = beam_search.run_beam_search(state.params, hps, enc_only)
    hps_m = hps.replace(dp=2, tp=1, sp=4, sp_attention="ring",
                        mode="decode")
    plan = mesh_lib.make_mesh(hps_m)
    fn = mesh_lib.make_sharded_beam_search(plan)
    sharded_params = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, plan.named(s)), state.params,
        mesh_lib.param_pspecs(state.params),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    out = fn(sharded_params, mesh_lib.shard_batch(plan, enc_only))
    np.testing.assert_array_equal(np.asarray(out.tokens), plain.tokens)
    np.testing.assert_array_equal(np.asarray(out.length), plain.length)


def test_tp_shards_megatron_layout(setup):
    hps, vocab, batch, state = setup
    plan = mesh_lib.make_mesh(hps.replace(dp=4, tp=2))
    sharded = mesh_lib.shard_train_state(plan, state)
    p = sharded.params
    assert p["embedding"].sharding.spec == mesh_lib.P("tp", None)
    assert p["out_bias"].sharding.spec == mesh_lib.P("tp")
    layer = p["decoder"]["layers"][0]
    assert layer["self_attn"]["wq"].sharding.spec == mesh_lib.P(None, "tp")
    assert layer["self_attn"]["wo"].sharding.spec == mesh_lib.P("tp", None)
    assert layer["ffn"]["w1"].sharding.spec == mesh_lib.P(None, "tp")
    assert layer["ffn"]["w2"].sharding.spec == mesh_lib.P("tp", None)
    assert layer["ln1"]["scale"].sharding.spec == mesh_lib.P()


def test_estimator_pipeline_with_transformer(tmp_path):
    """The reference's testInferenceAfterTraining path (fit -> transform,
    weights via checkpoint dir) with model_family=transformer selected
    through the hyper-params argv string — the full L6 pipeline surface."""
    import shlex

    from textsummarization_on_flink_tpu.pipeline import estimator as est_lib
    from textsummarization_on_flink_tpu.pipeline.io import (
        CollectionSink,
        CollectionSource,
        DataTypes,
    )

    words = ("article reference the a quick brown fox jumped over lazy dog "
             "0 1 2 3 4 5 6 7").split()
    vocab = Vocab(words=words)

    def hp(mode):
        hps = HParams(mode=mode, num_steps=2, batch_size=4, hidden_dim=8,
                      emb_dim=8, vocab_size=24, max_enc_steps=12,
                      max_dec_steps=6, beam_size=2, min_dec_steps=1,
                      max_oov_buckets=4, log_root=str(tmp_path),
                      exp_name="exp", model_family="transformer",
                      num_heads=2, enc_layers=1, dec_layers=1)
        return shlex.split(hps.to_argv())

    e = est_lib.SummarizationEstimator()
    (e.set_train_selected_cols(["uuid", "article", "reference"])
      .set_train_output_cols(["uuid"])
      .set_train_output_types([DataTypes.STRING]))
    e.set_train_hyper_params(hp("train"))
    (e.set_inference_selected_cols(["uuid", "article", "reference"])
      .set_inference_output_cols(["uuid", "article", "summary", "reference"])
      .set_inference_output_types([DataTypes.STRING] * 4))
    e.set_inference_hyper_params(hp("decode"))
    e.with_vocab(vocab)

    rows = [(f"uuid-{i}", f"article {i} .", "", f"reference {i} .")
            for i in range(8)]
    model = e.fit(CollectionSource(rows))
    sink = CollectionSink()
    model.with_vocab(vocab)
    model.transform(CollectionSource(rows), sink)
    assert len(sink.rows) == 8
    for uuid, article, summary, reference in sink.rows:
        assert uuid.startswith("uuid-")
        assert isinstance(summary, str)


def test_decoder_serving_end_to_end(setup, tmp_path):
    """BeamSearchDecoder serves the transformer through the same stack:
    checkpoint dir -> batcher -> beam search -> result rows."""
    from textsummarization_on_flink_tpu.checkpoint import (
        checkpointer as ckpt_lib,
    )
    from textsummarization_on_flink_tpu.data.batcher import Batcher
    from textsummarization_on_flink_tpu.decode import decoder as dec_lib

    hps, vocab, batch, state = setup
    dec_hps = hps.replace(mode="decode", batch_size=2, single_pass=False,
                          min_dec_steps=1)
    train_dir = str(tmp_path / "train")
    ckpt_lib.Checkpointer(train_dir, hps=dec_hps).save(state)

    def source():
        for i in range(2):
            yield (f"u{i}", f"w1 w2 w3 article {i}", "<s> w1 w2 . </s>", "r")

    batcher = Batcher("", vocab, dec_hps, single_pass=True,
                      decode_batch_mode="distinct", example_source=source)
    d = dec_lib.BeamSearchDecoder(dec_hps, vocab, batcher,
                                  train_dir=train_dir,
                                  decode_root=str(tmp_path / "dec"),
                                  max_ckpt_retries=0)
    rows = []
    d.decode(result_sink=lambda r: rows.append(r.as_row()), log_results=False)
    assert len(rows) == 2
    for uuid, art, summary, ref in rows:
        assert isinstance(summary, str)
