"""Transformer (BART-class) summarization model family, TPU-native.

The reference repository's only model is the LSTM pointer-generator
(/root/reference/src/main/python/pointer-generator/model.py); this module
is the framework's second model family ("BART-base behind the same
Estimator/Model API") — sharing every
surrounding subsystem: the same ``HParams``, the same ``Batch`` arrays,
the same ``TrainOutput`` contract consumed by the Trainer/Evaluator, the
same on-device beam search (via the beam-adapter protocol in
decode/beam_search.py), the same checkpointing and serving stack.

Architecture (TPU-first choices, not a port of any torch code):

  * pre-LN encoder-decoder with learned positional embeddings and a tied
    input/output embedding ([V, H] — the single biggest matrix, sharded
    over the tp mesh axis exactly like the pointer-generator's
    output_projection);
  * teacher-forced training is fully parallel over decode steps (one
    batched matmul chain — no scan), which is the transformer's
    structural advantage over the reference's 100-step unrolled LSTM
    graph (model.py:214);
  * the pointer/copy mechanism is preserved: the FINAL decoder layer's
    cross-attention (averaged over heads) is the copy distribution,
    ``p_gen = sigmoid(linear([h, cross_ctx]))`` mixes it with the vocab
    softmax, and training computes the gold mixture probability from raw
    logits (same math as ops/losses.gold_mixture_prob, deliberately
    inlined in log space so neither the [B, T, V] softmax nor the
    extended-vocab distribution is ever materialized);
  * coverage (``hps.coverage``) penalizes repeated cross-attention via
    the closed-form exclusive-cumsum coverage loss
    (ops/losses.coverage_loss).  Unlike the LSTM family, coverage does
    NOT feed back into attention energies — that mechanism is specific
    to the reference's additive attention (attention_decoder.py:113-123);
    here coverage is purely the training penalty;
  * incremental decoding uses a static-shape KV cache ([K, L, T, nh, hd]
    with a position mask) so the whole beam search stays inside one
    jitted while_loop;
  * attention logits, softmax, and layernorm run in f32; matmuls follow
    ``hps.compute_dtype`` (bf16 on the MXU).

No dropout: the reference trains without regularization
(run_summarization.py:62-74 has no dropout flag) and determinism keeps
step-parity tests exact.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from textsummarization_on_flink_tpu import config as config_lib
from textsummarization_on_flink_tpu import models as models_lib
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.models import pointer_generator as pg
from textsummarization_on_flink_tpu.ops import losses as loss_ops

Array = jax.Array
Params = Dict[str, Any]

TrainOutput = pg.TrainOutput  # same contract for Trainer/Evaluator


# --------------------------------------------------------------------------
# Shapes / init
# --------------------------------------------------------------------------

def _head_dim(hps: HParams) -> int:
    return hps.hidden_dim // hps.num_heads


def _init_attn(key: Array, H: int) -> Dict[str, Array]:
    ks = jax.random.split(key, 4)
    return {
        "wq": pg._glorot(ks[0], (H, H)),
        "wk": pg._glorot(ks[1], (H, H)),
        "wv": pg._glorot(ks[2], (H, H)),
        "wo": pg._glorot(ks[3], (H, H)),
    }


def _init_ln(H: int) -> Dict[str, Array]:
    return {"scale": jnp.ones((H,), jnp.float32),
            "bias": jnp.zeros((H,), jnp.float32)}


def _init_ffn(key: Array, H: int, F: int) -> Dict[str, Array]:
    k1, k2 = jax.random.split(key)
    return {"w1": pg._glorot(k1, (H, F)), "b1": jnp.zeros((F,), jnp.float32),
            "w2": pg._glorot(k2, (F, H)), "b2": jnp.zeros((H,), jnp.float32)}


def init_params(hps: HParams, vsize: int, key: Array) -> Params:
    """Parameter pytree.  Top-level ``embedding`` is [V, H] (same name and
    vocab-leading layout as the pointer-generator so mesh tp-sharding and
    divisibility validation apply unchanged)."""
    H, F = hps.hidden_dim, hps.ffn_width
    n_keys = 3 + 2 * hps.enc_layers + 3 * hps.dec_layers + 1
    keys = iter(jax.random.split(key, n_keys))

    enc_layers = []
    for _ in range(hps.enc_layers):
        enc_layers.append({
            "ln1": _init_ln(H), "self_attn": _init_attn(next(keys), H),
            "ln2": _init_ln(H), "ffn": _init_ffn(next(keys), H, F),
        })
    dec_layers = []
    for _ in range(hps.dec_layers):
        dec_layers.append({
            "ln1": _init_ln(H), "self_attn": _init_attn(next(keys), H),
            "ln_cross": _init_ln(H), "cross_attn": _init_attn(next(keys), H),
            "ln2": _init_ln(H), "ffn": _init_ffn(next(keys), H, F),
        })
    return {
        "embedding": pg._trunc_normal(next(keys), (vsize, H), 0.02),
        "pos_enc": pg._trunc_normal(next(keys), (hps.max_enc_steps, H), 0.02),
        "pos_dec": pg._trunc_normal(next(keys), (hps.max_dec_steps + 1, H),
                                    0.02),
        "encoder": {"layers": enc_layers, "ln_out": _init_ln(H)},
        "decoder": {"layers": dec_layers, "ln_out": _init_ln(H)},
        "pgen_linear": {"kernel": pg._glorot(next(keys), (2 * H, 1)),
                        "bias": jnp.zeros((1,), jnp.float32)},
        "out_bias": jnp.zeros((vsize,), jnp.float32),
    }


# --------------------------------------------------------------------------
# Core blocks
# --------------------------------------------------------------------------

def _ln(p: Dict[str, Array], x: Array) -> Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + 1e-6)
            * p["scale"] + p["bias"]).astype(x.dtype)


def _split_heads(hps: HParams, x: Array) -> Array:
    """[..., H] -> [..., nh, hd]"""
    return x.reshape(x.shape[:-1] + (hps.num_heads, _head_dim(hps)))


def _merge_heads(x: Array) -> Array:
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def _mha(hps: HParams, p: Dict[str, Array], q_in: Array, kv_in: Array,
         mask: Array) -> Tuple[Array, Array]:
    """Multi-head attention.

    q_in: [..., Tq, H]; kv_in: [..., Tk, H]; mask: broadcastable to
    [..., Tq, Tk] (1 = attend).  Returns (output [..., Tq, H],
    head-averaged probabilities [..., Tq, Tk] in f32).
    """
    # compute in the activation dtype: master params are f32, cast per
    # use (bf16 activations @ f32 weights would silently PROMOTE the
    # matmul back to f32 — half the MXU's bf16 rate); accumulation stays
    # f32 via preferred_element_type
    dt = q_in.dtype
    q = _split_heads(hps, q_in @ p["wq"].astype(dt))  # [..., Tq, nh, hd]
    k = _split_heads(hps, kv_in @ p["wk"].astype(dt))
    v = _split_heads(hps, kv_in @ p["wv"].astype(dt))
    scale = _head_dim(hps) ** -0.5
    logits = jnp.einsum("...qnd,...knd->...nqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits * scale
    neg = jnp.asarray(-1e30, jnp.float32)
    logits = jnp.where(mask[..., None, :, :] > 0, logits, neg)
    probs = jax.nn.softmax(logits, axis=-1)
    # a fully-masked query row gives a uniform softmax over -1e30 logits;
    # zero it so padding queries emit exact zeros (matches the clamped
    # masked_softmax semantics in ops/attention.py)
    any_key = jnp.sum(mask[..., None, :, :], axis=-1, keepdims=True) > 0
    probs = jnp.where(any_key, probs, 0.0)
    ctx = jnp.einsum("...nqk,...knd->...qnd", probs.astype(dt), v,
                     preferred_element_type=jnp.float32).astype(dt)
    out = _merge_heads(ctx) @ p["wo"].astype(dt)
    return out, jnp.mean(probs, axis=-3)  # head-avg [..., Tq, Tk]


def _ffn_block(p: Dict[str, Array], x: Array) -> Array:
    dt = x.dtype  # see _mha: keep the matmuls in the activation dtype
    h = jax.nn.gelu(x @ p["w1"].astype(dt) + p["b1"].astype(dt))
    return h @ p["w2"].astype(dt) + p["b2"].astype(dt)


def _use_flash(hps: HParams, T: int) -> bool:
    """Route self-attention through the Pallas TPU flash kernel when it
    pays off: long sequences at head widths the kernel tiles natively
    (the [B, nh, T, T] score tensor never hits HBM).  TS_FLASH=on forces
    it on ANY shape — unaligned T/head_dim are zero-padded to the 128
    grid by the caller (exact numerics; extra FLOPs), which is the
    roofline-motivated A/B for the bandwidth-bound reference scale
    (T=400, hd=32 — scripts/roofline.py: the einsum path's materialized
    f32 score tensors dominate the transformer step's bytes).  =off
    disables; auto (the FROZEN default) keeps the conservative
    natively-aligned T>=1024 rule.  The kernel is TPU-only (its Mosaic
    lowering has no CPU/GPU path): under auto a non-TPU backend takes
    the einsum formula, and TS_FLASH=on there is an error, never a
    silent formula run under the kernel's name.  Cross-attention never
    uses it — its probabilities ARE the copy distribution and must be
    materialized anyway."""
    from textsummarization_on_flink_tpu.config import flash_mode_from_env

    mode = flash_mode_from_env()
    if mode == "off":
        return False
    on_tpu = jax.default_backend() == "tpu"
    if mode == "on":
        if not on_tpu:
            raise ValueError(
                f"TS_FLASH=on needs a TPU backend (the Pallas flash "
                f"kernel has no {jax.default_backend()!r} lowering); "
                f"unset it or use TS_FLASH=auto|off")
        return True
    hd = _head_dim(hps)
    aligned = T % 128 == 0 and hd % 128 == 0
    return on_tpu and aligned and T >= 1024


def _self_attention(hps: HParams, p: Dict[str, Array], x_norm: Array,
                    pad_mask: Optional[Array], causal: bool) -> Array:
    """Self-attention block used by the encoder (padding mask) and the
    training decoder (causal).  Dispatch order: sequence-parallel
    attention when --sp_attention=ring|ulysses under an sp>1 mesh, then
    the Pallas flash kernel on eligible shapes, then the einsum formula."""
    T = x_norm.shape[-2]
    sp_mesh = None
    if hps.sp_attention and not causal and pad_mask is not None:
        from textsummarization_on_flink_tpu.parallel import (
            ring_attention as ra,
        )

        mesh = ra.current_mesh()
        if mesh is not None and mesh.shape.get("sp", 1) > 1:
            sp_mesh = mesh
    use_flash = sp_mesh is None and _use_flash(hps, T)
    if sp_mesh is not None or use_flash:
        # shared head projection for both kernel paths — one site to
        # change if the projection ever grows biases or dtype casts;
        # params cast to the activation dtype like _mha
        dt = x_norm.dtype
        q = _split_heads(hps, x_norm @ p["wq"].astype(dt))  # [B, T, nh, hd]
        k = _split_heads(hps, x_norm @ p["wk"].astype(dt))
        v = _split_heads(hps, x_norm @ p["wv"].astype(dt))
        sm_scale = _head_dim(hps) ** -0.5
    if sp_mesh is not None:
        # the ring/ulysses kernels accumulate logits and context in the
        # input dtype (ring_attention.py) — hand them f32 q/k/v so the
        # module invariant 'attention logits, softmax run in f32' holds
        # on the sp path too; the projections above still ran at bf16
        fn = ra.make_sp_attention(sp_mesh, hps.sp_attention, "sp")
        ctx = _merge_heads(fn(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), pad_mask, sm_scale))
        # downcast the f32-accumulated context before the wo matmul, like
        # _mha — else the projection runs at the MXU's f32 rate
        return ctx.astype(dt) @ p["wo"].astype(dt)
    if use_flash:
        from jax.experimental.pallas.ops.tpu import flash_attention as fa

        q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))  # [B,nh,T,hd]
        hd = q.shape[-1]
        t_pad, hd_pad = -T % 128, -hd % 128
        if t_pad or hd_pad:
            # zero-pad to the kernel's 128-lane grid (TS_FLASH=on at
            # unaligned shapes, e.g. reference scale T=400 hd=32).
            # Exact numerics: zero head-dim columns change no dot
            # product and their output columns are sliced away; zero
            # key rows are excluded from real queries by the padding
            # segment (non-causal) or live strictly in the future
            # (causal); padded-tail query rows are sliced away.
            widths = [(0, 0), (0, 0), (0, t_pad), (0, hd_pad)]
            q, k, v = (jnp.pad(t, widths) for t in (q, k, v))
        seg = None
        if not causal:
            # padding keys (article padding AND the alignment tail) live
            # in a different segment than real tokens, so real queries
            # never attend them (padding queries produce garbage rows
            # that downstream masks discard)
            pm = pad_mask if pad_mask is not None \
                else jnp.ones((q.shape[0], T), q.dtype)
            if t_pad:
                pm = jnp.pad(pm, [(0, 0), (0, t_pad)])
            ids = (pm <= 0).astype(jnp.int32)  # [B, T+t_pad]
            seg = fa.SegmentIds(q=ids, kv=ids)
        out = fa.flash_attention(q, k, v, segment_ids=seg, causal=causal,
                                 sm_scale=sm_scale)
        if t_pad or hd_pad:
            out = out[:, :, :T, :hd]
        ctx = _merge_heads(jnp.swapaxes(out, 1, 2))
        return ctx @ p["wo"].astype(ctx.dtype)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), jnp.float32))[None]
    else:
        mask = pad_mask[:, None, :]
    out, _ = _mha(hps, p, x_norm, x_norm, mask)
    return out


def _encoder_stack(params: Params, hps: HParams, x: Array,
                   enc_mask: Array) -> Array:
    """x: [B, T_enc, H]; enc_mask: [B, T_enc] -> [B, T_enc, H] (f32)."""

    def layer_fn(layer, x, enc_mask):
        a = _self_attention(hps, layer["self_attn"], _ln(layer["ln1"], x),
                            enc_mask, causal=False)
        x = x + a
        return x + _ffn_block(layer["ffn"], _ln(layer["ln2"], x))

    if hps.remat:  # recompute layer activations in backward (HBM <- FLOPs)
        layer_fn = jax.checkpoint(layer_fn)
    for layer in params["encoder"]["layers"]:
        x = layer_fn(layer, x, enc_mask)
    return _ln(params["encoder"]["ln_out"], x).astype(jnp.float32)


# --------------------------------------------------------------------------
# Training forward (fully parallel over decode steps)
# --------------------------------------------------------------------------

class TransformerEncView(NamedTuple):
    """Per-batch encoder view for decoding: the per-layer cross-attention
    K/V, precomputed once per article (the raw encoder states are fully
    consumed by this projection — no other decode-time reader)."""

    cross_k: Array  # [B, L, T_enc, nh, hd]
    cross_v: Array  # [B, L, T_enc, nh, hd]


def _embed_enc(params: Params, hps: HParams, enc_batch: Array) -> Array:
    T = enc_batch.shape[-1]
    x = params["embedding"][enc_batch] + params["pos_enc"][:T]
    return pg._cast(hps, x)


def _embed_dec(params: Params, hps: HParams, tokens: Array,
               positions: Array) -> Array:
    x = params["embedding"][tokens] + params["pos_dec"][positions]
    return pg._cast(hps, x)


def forward_train(params: Params, hps: HParams, arrays: Dict[str, Array],
                  ) -> TrainOutput:
    """Teacher-forced training/eval forward pass -> TrainOutput.

    Same loss semantics as the pointer-generator family: masked-average
    pointer NLL + optional coverage penalty on the copy attention.  The
    gold mixture probability is computed from raw logits (the same math
    as ops/losses.gold_mixture_prob, inlined in log space so the
    [B, T, V] softmax is never materialized)."""
    enc_mask = arrays["enc_padding_mask"]  # [B, T_enc]
    T_dec = arrays["dec_batch"].shape[1]

    x = _embed_enc(params, hps, arrays["enc_batch"])
    enc_out = _encoder_stack(params, hps, x, enc_mask)
    enc_out_c = pg._cast(hps, enc_out)

    y = _embed_dec(params, hps, arrays["dec_batch"], jnp.arange(T_dec))
    cross_mask = enc_mask[:, None, :]  # [B, 1, T_enc]

    def layer_fn(layer, y, enc_out_c, cross_mask):
        a = _self_attention(hps, layer["self_attn"], _ln(layer["ln1"], y),
                            None, causal=True)
        y = y + a
        c, probs = _mha(hps, layer["cross_attn"], _ln(layer["ln_cross"], y),
                        enc_out_c, cross_mask)
        y = y + c
        y = y + _ffn_block(layer["ffn"], _ln(layer["ln2"], y))
        return y, c, probs

    if hps.remat:
        layer_fn = jax.checkpoint(layer_fn)
    attn_dist = None
    for layer in params["decoder"]["layers"]:
        y, c, probs = layer_fn(layer, y, enc_out_c, cross_mask)
        attn_dist = probs  # final layer's head-averaged copy distribution
        cross_ctx = c
    h = _ln(params["decoder"]["ln_out"], y).astype(jnp.float32)
    return train_output_tail(params, hps, arrays, h, cross_ctx, attn_dist)


def _head_operands(params: Params, hps: HParams, h: Array,
                   ) -> Tuple[Array, Array]:
    """(x, w) with the vocabulary scores ``x @ w + out_bias``: the tied
    embedding under h, or the factored head's w2 [r, V] under h @ w1."""
    vh = params.get("vocab_head")
    if vh is not None:
        return loss_ops.project_scores(h, vh["w1"], hps.compute_dtype), \
            vh["w2"]
    return h, params["embedding"].T


def vocab_scores_of(params: Params, hps: HParams, h: Array) -> Array:
    """Raw vocabulary scores for final-LN decoder states ``h``
    [..., H_dec]: the tied-embedding projection, or — when the family
    carries a factored low-rank head (the distilled narrow draft,
    ISSUE 12) — ``(h @ w1) @ w2`` with w1 [H_d, r], w2 [r, V], never
    materializing the [H_d, V] product.  ONE source: the train loss
    head routes the projection through here and every decode output
    tail through the same ``_head_operands``, so the two heads cannot
    drift.  Both factored matmuls route
    through the ONE dtype-aware projection (ops/losses.project_scores,
    bf16 operands + f32 accumulation under compute_dtype=bfloat16) —
    same kernel as the tied branch and the streaming chunk bodies."""
    x, w = _head_operands(params, hps, h)
    return pg._proj(hps, x, w) + params["out_bias"]


def vocab_proj_weight(params: Params) -> Array:
    """[H_dec, V] dense projection matrix for the STREAMING loss
    kernels (ops/losses), which consume one weight matrix: the tied
    embedding transpose, or the materialized w1 @ w2 of the factored
    head (parameter-sized — r*V*H_d FLOPs once per step, amortized
    over B*T_dec positions).  Factored-head caveat: the streaming path
    projects h @ (w1 @ w2) while ``vocab_scores_of`` computes
    (h @ w1) @ w2, so loss_chunk on/off agree to matmul-association
    tolerance for factored heads, not bitwise (the tied head stays
    exact — identical W, identical kernel)."""
    vh = params.get("vocab_head")
    if vh is not None:
        return vh["w1"] @ vh["w2"]
    return params["embedding"].T


def train_output_tail(params: Params, hps: HParams, arrays: Dict[str, Array],
                      h: Array, cross_ctx: Array, attn_dist: Array,
                      ) -> TrainOutput:
    """The loss head shared by every transformer-shaped decoder family
    (transformer, avg_attention — including the factored-head narrow
    draft): p_gen from [h, cross_ctx], vocab projection via
    ``vocab_scores_of`` (streamed when --loss_chunk, materialized
    otherwise), pointer mixture or baseline CE, coverage penalty.  ONE
    source for the mixture math keeps the families' losses from
    drifting.

    h: [B, T_dec, H_dec] final-LN decoder states (f32); cross_ctx:
    final layer's cross-attention output; attn_dist: its head-averaged
    copy distribution [B, T_dec, T_enc].
    """
    dec_mask = arrays["dec_padding_mask"]  # [B, T_dec]

    p_gens = jax.nn.sigmoid(
        jnp.concatenate([h, cross_ctx.astype(jnp.float32)], axis=-1)
        @ params["pgen_linear"]["kernel"]
        + params["pgen_linear"]["bias"])[..., 0]  # [B, T_dec]

    targets = arrays["target_batch"]
    if hps.loss_chunk > 0:
        # streaming chunked loss (PERF.md byte diet): the [B, T_dec, V]
        # tied-projection logits never materialize — ops/losses streams
        # [chunk, B, V] blocks with a backward that recomputes them.
        # Step-major views for the shared streaming kernels.
        h_t = jnp.swapaxes(h, 0, 1)  # [T_dec, B, H]
        targets_t = jnp.swapaxes(targets, 0, 1)
        if hps.pointer_gen:
            gold_t = loss_ops.streaming_gold_probs(
                h_t, jnp.swapaxes(attn_dist, 0, 1),
                jnp.swapaxes(p_gens, 0, 1), targets_t,
                arrays["enc_batch_extend_vocab"],
                vocab_proj_weight(params), params["out_bias"],
                chunk=hps.loss_chunk, compute_dtype=hps.compute_dtype)
            gold = jnp.swapaxes(gold_t, 0, 1)
            loss = loss_ops.mask_and_avg(-jnp.log(gold + 1e-10), dec_mask)
        else:
            loss = loss_ops.streaming_softmax_cross_entropy(
                h_t, targets_t, jnp.swapaxes(dec_mask, 0, 1),
                vocab_proj_weight(params), params["out_bias"],
                chunk=hps.loss_chunk, compute_dtype=hps.compute_dtype)
    else:
        logits = vocab_scores_of(params, hps, h)  # [B, T_dec, V]
        if hps.pointer_gen:
            # gold prob without materializing the [B, T, V] softmax —
            # the SAME mixture math as the pg family and the streaming
            # path (one source of truth), on step-major views
            gold = jnp.swapaxes(loss_ops.gold_mixture_prob_from_scores(
                jnp.swapaxes(logits, 0, 1), jnp.swapaxes(attn_dist, 0, 1),
                jnp.swapaxes(p_gens, 0, 1), jnp.swapaxes(targets, 0, 1),
                arrays["enc_batch_extend_vocab"]), 0, 1)
            loss = loss_ops.mask_and_avg(-jnp.log(gold + 1e-10), dec_mask)
        else:
            loss = loss_ops.softmax_cross_entropy_baseline(
                logits, targets, dec_mask)
    if hps.coverage:
        cov_loss = loss_ops.coverage_loss(attn_dist, dec_mask)
    else:
        cov_loss = jnp.zeros(())
    total = loss + hps.cov_loss_wt * cov_loss
    return TrainOutput(loss=loss, coverage_loss=cov_loss, total_loss=total,
                       attn_dists=attn_dist, p_gens=p_gens)


# --------------------------------------------------------------------------
# Decoding (KV-cache incremental step + beam adapter)
# --------------------------------------------------------------------------

def beam_encode(params: Params, hps: HParams, arrays: Dict[str, Array],
                head_hps: Optional[HParams] = None) -> TransformerEncView:
    """Encode a batch once and precompute per-layer cross-attention K/V
    (leaves have a leading batch axis; vmapped per-article downstream).

    ``head_hps`` carries the DECODER-side width for the head split (the
    narrow AAN draft's H_d — its rectangular [H, H_d] K/V kernels make
    this precompute the encoder-view boundary projection, ISSUE 12);
    None = hps (the transformer itself).  ONE body for both families —
    a numerics change here reaches every encoder view."""
    head_hps = head_hps if head_hps is not None else hps
    x = _embed_enc(params, hps, arrays["enc_batch"])
    enc_out = _encoder_stack(params, hps, x, arrays["enc_padding_mask"])
    enc_c = pg._cast(hps, enc_out)
    dt = enc_c.dtype  # keep the K/V precompute matmuls in the cast dtype
    ks, vs = [], []
    for layer in params["decoder"]["layers"]:
        p = layer["cross_attn"]
        ks.append(_split_heads(head_hps, enc_c @ p["wk"].astype(dt)))
        vs.append(_split_heads(head_hps, enc_c @ p["wv"].astype(dt)))
    return TransformerEncView(cross_k=jnp.stack(ks, axis=1),
                              cross_v=jnp.stack(vs, axis=1))


BeamStepOut = pg.BeamStepOut  # shared beam protocol output type


@jax.named_scope("attention")
def cross_attend_layer(hps: HParams, layer: Dict[str, Any], y: Array,
                       ck: Array, cv: Array, enc_mask: Array,
                       nb: Optional[Array] = None,
                       ) -> Tuple[Array, Array]:
    """One decoder layer's cross-attention against its precomputed
    per-article K/V (``TransformerEncView`` slices) for a stack of R
    query rows — beam hypotheses, verify positions, or the AAN draft's
    rows all share this ONE block (the decode-side analogue of
    ``train_output_tail``'s factoring: a numerics fix lands once).

    y: [R, H]; ck/cv: [T_enc, nh, hd]; enc_mask: [T_enc].  Returns
    (cross_out [R, H] — NOT yet residual-added — and the head-averaged
    probabilities [R, T_enc], f32).

    ``nb`` (length-masked slot decode, ISSUE 11): traced active-block
    count — the logits/context einsums run as a statically-unrolled
    chain of ``resolve_enc_block(hps)``-position key blocks, each gated
    by a real XLA conditional on ``b < nb``, so the K/V bytes streamed
    per step scale with the longest active resident's TRUE article
    length.  Uncovered blocks sit at the masked-logit floor (exactly
    where enc_mask=0 keys sit in the dense path), so softmax weights
    there are 0 and skipped context blocks contribute exactly nothing;
    the result differs from dense only by block-wise partial-sum
    association.  nb=None keeps the dense einsums."""
    hd = _head_dim(hps)
    dt = y.dtype
    cp = layer["cross_attn"]
    qc = _split_heads(hps, _ln(layer["ln_cross"], y) @ cp["wq"].astype(dt))
    q32 = qc.astype(jnp.float32)
    if nb is None:
        clogits = jnp.einsum("knd,tnd->knt", q32,
                             ck.astype(jnp.float32)) * (hd ** -0.5)
        clogits = jnp.where(enc_mask[None, None, :] > 0, clogits, -1e30)
    else:
        T = enc_mask.shape[0]
        block = config_lib.resolve_enc_block(hps)
        nblocks = -(-T // block)
        clogits = jnp.full(q32.shape[:2] + (T,), -1e30, jnp.float32)
        for b in range(nblocks):
            lo, hi = b * block, min((b + 1) * block, T)

            def write_block(cl, lo=lo, hi=hi):
                lb = jnp.einsum("knd,tnd->knt", q32,
                                ck[lo:hi].astype(jnp.float32)) * (hd ** -0.5)
                lb = jnp.where(enc_mask[lo:hi][None, None, :] > 0, lb, -1e30)
                return cl.at[:, :, lo:hi].set(lb)

            clogits = jax.lax.cond(b < nb, write_block, lambda cl: cl,
                                   clogits)
    cprobs = jax.nn.softmax(clogits, axis=-1)
    any_key = jnp.sum(enc_mask) > 0
    cprobs = jnp.where(any_key, cprobs, 0.0)
    if nb is None:
        cctx = jnp.einsum("knt,tnd->knd", cprobs, cv.astype(jnp.float32))
    else:
        cctx = jnp.zeros(q32.shape, jnp.float32)
        for b in range(nblocks):
            lo, hi = b * block, min((b + 1) * block, T)

            def add_block(cc, lo=lo, hi=hi):
                return cc + jnp.einsum("knt,tnd->knd", cprobs[:, :, lo:hi],
                                       cv[lo:hi].astype(jnp.float32))

            cctx = jax.lax.cond(b < nb, add_block, lambda cc: cc, cctx)
    cross_out = _merge_heads(cctx).astype(dt) @ cp["wo"].astype(dt)
    return cross_out, jnp.mean(cprobs, axis=1)


@jax.named_scope("vocab_dist")
def decode_output_tail(params: Params, hps: HParams, y: Array,
                       cross_ctx: Array, attn_dist: Array, ext_ids: Array,
                       k: int, head=None,
                       ) -> Tuple[Array, Array, Array, Array]:
    """Decoder output head shared by every transformer-shaped decode
    path (beam adapter step, ``spec_verify``, the AAN step): final LN,
    vocab projection as ``vocab_scores_of`` makes it (tied, or the
    narrow draft's factored head), p_gen, and the k best of the pointer
    mixture (``pg.step_top_k``; its selection over the vocabulary
    carries the ``topk`` scope).  ``head``: the article's ``beam_head``
    from a caller that decodes in a loop, or None.  Returns (topk_probs
    [R, k], topk_ids [R, k], p_gen [R], h [R, H_dec] f32)."""
    h = _ln(params["decoder"]["ln_out"], y).astype(jnp.float32)
    x, w = _head_operands(params, hps, h)
    vocab_scores = pg._proj(hps, x, w) + params["out_bias"]
    p_gen = jax.nn.sigmoid(
        jnp.concatenate([h, cross_ctx.astype(jnp.float32)], axis=-1)
        @ params["pgen_linear"]["kernel"]
        + params["pgen_linear"]["bias"])[:, 0]
    topk_probs, topk_ids = pg.step_top_k(hps, vocab_scores, attn_dist, p_gen,
                                         ext_ids, k,
                                         pg.head_scores(hps, x, head))
    return topk_probs, topk_ids, p_gen, h


def beam_head(params: Params, hps: HParams, ext_ids: Array):
    """The vocabulary head (``_head_operands``' w) at the articles' ids
    (pg.head_at); the AAN family's too."""
    vh = params.get("vocab_head")
    w = vh["w2"] if vh is not None else params["embedding"].T
    return pg.head_at(hps, w, params["out_bias"], ext_ids)


def beam_adapter(hps: HParams):
    """Beam-search protocol: (init_state, step) closures over params.

    State leaves all carry a leading beam axis K so the search can gather
    surviving hypotheses with one tree_map.  The KV cache is static-shape
    [K, L, T_dec+1, nh, hd]; position validity comes from the step index.
    """
    K = hps.beam_size
    L = hps.dec_layers
    nh, hd = hps.num_heads, _head_dim(hps)
    T = hps.max_dec_steps + 1
    # --decode_cache_dtype=bfloat16 (decode byte diet, ISSUE 7): the
    # cache is the dominant per-hypothesis resident tensor; bf16 storage
    # halves it and its per-step traffic.  The einsums below widen to
    # f32 before the logits/softmax, so the attention MATH is unchanged
    # — only the HBM representation narrows (drift envelope pinned).
    cache_dtype = (jnp.bfloat16 if hps.decode_cache_dtype == "bfloat16"
                   else jnp.float32)

    def init_state(params: Params, enc_one: TransformerEncView):
        del params, enc_one
        return {
            "cache_k": jnp.zeros((K, L, T, nh, hd), cache_dtype),
            "cache_v": jnp.zeros((K, L, T, nh, hd), cache_dtype),
        }

    def step(params: Params, enc_one: TransformerEncView, enc_mask: Array,
             ext_ids: Array, t: Array, latest: Array, state, nb=None,
             head=None):
        """enc_one leaves are per-article (no batch axis); latest: [K].
        nb: traced active-block count for the length-masked slot path
        (None = dense cross-attention, the batch-search default);
        head: the article's ``beam_head``, or None."""
        y = _embed_dec(params, hps, latest, t)  # [K, H]
        pos_ok = (jnp.arange(T) <= t).astype(jnp.float32)  # [T]
        cache_k, cache_v = state["cache_k"], state["cache_v"]
        attn_dist = None
        dt = y.dtype  # projections in the activation dtype (see _mha);
        # the cache and softmaxes below deliberately stay f32
        for li, layer in enumerate(params["decoder"]["layers"]):
            p = layer["self_attn"]
            with jax.named_scope("attention"):
                h_norm = _ln(layer["ln1"], y)
                q = _split_heads(hps, h_norm @ p["wq"].astype(dt))  # [K, nh, hd]
                k_new = _split_heads(hps, h_norm @ p["wk"].astype(dt))
                v_new = _split_heads(hps, h_norm @ p["wv"].astype(dt))
                cache_k = cache_k.at[:, li, t].set(k_new.astype(cache_dtype))
                cache_v = cache_v.at[:, li, t].set(v_new.astype(cache_dtype))
                # widen the (possibly bf16) cache at the point of use: the
                # logits einsum and softmax stay f32 whatever the storage
                kk = cache_k[:, li].astype(jnp.float32)  # [K, T, nh, hd]
                vv = cache_v[:, li].astype(jnp.float32)
                logits = jnp.einsum("knd,ktnd->knt", q.astype(jnp.float32),
                                    kk)
                logits = logits * (hd ** -0.5)
                logits = jnp.where(pos_ok[None, None, :] > 0, logits, -1e30)
                probs = jax.nn.softmax(logits, axis=-1)
                ctx = jnp.einsum("knt,ktnd->knd", probs, vv)
                y = y + _merge_heads(ctx).astype(dt) @ p["wo"].astype(dt)
            # cross attention against the precomputed per-layer K/V
            cross_out, attn_dist = cross_attend_layer(
                hps, layer, y, enc_one.cross_k[li], enc_one.cross_v[li],
                enc_mask, nb=nb)
            y = y + cross_out
            y = y + _ffn_block(layer["ffn"], _ln(layer["ln2"], y))
            cross_ctx = cross_out
        topk_probs, topk_ids, p_gen, _ = decode_output_tail(
            params, hps, y, cross_ctx, attn_dist, ext_ids, 2 * hps.beam_size,
            head)
        return BeamStepOut(topk_ids=topk_ids,
                           topk_log_probs=jnp.log(topk_probs + 1e-10),
                           attn_dist=attn_dist, p_gen=p_gen,
                           state={"cache_k": cache_k, "cache_v": cache_v})

    return init_state, step


#: the length-masked slot-decode adapter (ISSUE 11): the shared
#: protocol wrapper threads the traced block count into this family's
#: step, where it bounds the per-layer cross-attention block chain
beam_adapter_masked = models_lib.masked_adapter(beam_adapter)


def pad_enc_view(enc_view: TransformerEncView, t_target: int,
                 ) -> TransformerEncView:
    """Zero-pad a bucket-width encoder view's key axis to ``t_target``
    (the prefill -> pack hand-off, decode/beam_search.prefill_jit): the
    padded K/V positions sit behind the valid-length mask, so they are
    never attended — zeros keep the 0-weight context products exact."""
    def pad(x):
        if x.shape[2] >= t_target:
            return x
        widths = [(0, 0)] * x.ndim
        widths[2] = (0, t_target - x.shape[2])
        return jnp.pad(x, widths)

    return TransformerEncView(cross_k=pad(enc_view.cross_k),
                              cross_v=pad(enc_view.cross_v))


# --------------------------------------------------------------------------
# Speculative verify (parallel multi-position teacher-forced scoring)
# --------------------------------------------------------------------------

def spec_init_state(hps: HParams, spec_k: int) -> Dict[str, Array]:
    """Single-hypothesis KV cache for the speculative verifier
    (decode/speculative.py): [L, W, nh, hd] with W = max_dec_steps +
    spec_k + 1, wide enough that a verify block starting at the last
    in-horizon step (t = T-1) writes its k+1 entries without clamping.
    Position validity comes from the committed step counter, exactly
    like the incremental adapter's cache — rejected draft positions are
    simply never attended and the next block overwrites them."""
    L = hps.dec_layers
    nh, hd = hps.num_heads, _head_dim(hps)
    W = hps.max_dec_steps + spec_k + 1
    cache_dtype = (jnp.bfloat16 if hps.decode_cache_dtype == "bfloat16"
                   else jnp.float32)
    return {
        "cache_k": jnp.zeros((L, W, nh, hd), cache_dtype),
        "cache_v": jnp.zeros((L, W, nh, hd), cache_dtype),
    }


def spec_verify(params: Params, hps: HParams, enc_one: TransformerEncView,
                enc_mask: Array, ext_ids: Array, t0: Array, tokens: Array,
                state: Dict[str, Array]):
    """Score S = spec_k + 1 teacher-forced positions in ONE parallel
    decoder pass — the speculative fast path's "one fat step" for the
    full model (decode/speculative.py; ISSUE 10).

    ``tokens`` [S] are the inputs consumed at steps t0 .. t0+S-1 (the
    last committed token followed by the draft's proposals, already
    OOV→UNK mapped by the caller).  Each position's Q attends the cache
    entries at positions <= its own step — the SAME masked-softmax the
    incremental ``beam_adapter`` step computes, just batched over the S
    query rows (extra masked columns contribute exact zeros, so the
    per-position numerics match the K=1 incremental step; the spec
    exactness tests pin this).  Returns per-position
    ``(topk_ids [S, 2], topk_log_probs [S, 2], attn_dist [S, T_enc],
    p_gen [S], state')`` where state' holds all S cache entries —
    append-only: acceptance never rolls the cache back, the committed
    step counter does.
    """
    S = tokens.shape[0]
    hd = _head_dim(hps)
    W = state["cache_k"].shape[1]
    cache_dtype = state["cache_k"].dtype
    pos = t0 + jnp.arange(S)  # [S] absolute decode steps
    y = _embed_dec(params, hps, tokens, pos)  # [S, H]
    dt = y.dtype
    cache_k, cache_v = state["cache_k"], state["cache_v"]
    pos_ok = jnp.arange(W)[None, :] <= pos[:, None]  # [S, W]
    attn_dist = None
    for li, layer in enumerate(params["decoder"]["layers"]):
        p = layer["self_attn"]
        h_norm = _ln(layer["ln1"], y)
        q = _split_heads(hps, h_norm @ p["wq"].astype(dt))  # [S, nh, hd]
        k_new = _split_heads(hps, h_norm @ p["wk"].astype(dt))
        v_new = _split_heads(hps, h_norm @ p["wv"].astype(dt))
        cache_k = cache_k.at[li, pos].set(k_new.astype(cache_dtype))
        cache_v = cache_v.at[li, pos].set(v_new.astype(cache_dtype))
        kk = cache_k[li].astype(jnp.float32)  # [W, nh, hd]
        vv = cache_v[li].astype(jnp.float32)
        logits = jnp.einsum("snd,tnd->snt", q.astype(jnp.float32), kk)
        logits = logits * (hd ** -0.5)
        logits = jnp.where(pos_ok[:, None, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        ctx = jnp.einsum("snt,tnd->snd", probs, vv)
        y = y + _merge_heads(ctx).astype(dt) @ p["wo"].astype(dt)
        cross_out, attn_dist = cross_attend_layer(
            hps, layer, y, enc_one.cross_k[li], enc_one.cross_v[li],
            enc_mask)
        y = y + cross_out
        y = y + _ffn_block(layer["ffn"], _ln(layer["ln2"], y))
        cross_ctx = cross_out
    topk_probs, topk_ids, p_gen, _ = decode_output_tail(
        params, hps, y, cross_ctx, attn_dist, ext_ids, 2)
    return (topk_ids, jnp.log(topk_probs + 1e-10), attn_dist, p_gen,
            {"cache_k": cache_k, "cache_v": cache_v})
