"""Fused additive attention + coverage as a Pallas TPU kernel.

The hot op of the pointer-generator (SURVEY.md §7.2 step 7): per decoder
step the reference computes, over every encoder position i
(/root/reference/src/main/python/pointer-generator/attention_decoder.py:79-129),

    e_i  = v . tanh(W_h h_i + W_s s_t [+ w_c c_i] + b)
    a    = masked_softmax(e)
    ctx  = sum_i a_i h_i

The XLA path (ops/attention.py) materializes the [B, T, D] `feats` tensor
in HBM between the add and the tanh reduction.  This kernel fuses energy,
masked softmax, and the context matmul into ONE pass per batch row: the
encoder tensors stream HBM->VMEM once, the [T, D] intermediate never
leaves VMEM, the context reduction rides the MXU.

At reference scale (T=400->pad 512, D=512, f32) one row's working set is
~2 MB — comfortably inside the ~16 MB VMEM budget, so the grid is simply
(B,) with full-[T, D] blocks.  (A T-blocked flash-style variant is the
obvious extension for long-context configs; see sp-axis notes in
parallel/mesh.py.)

Masking parity: the reference softmaxes THEN masks THEN renormalizes
(attention_decoder.py:96-101); energy-level -inf masking is algebraically
identical and is what the kernel does.

Training support: `fused_attention` carries a custom VJP whose backward
recomputes the (cheap) reference formula under XLA autodiff — kernel
forward speed, reference-exact gradients, no handwritten backward to
maintain.
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

NEG = -1e30
_LANE = 128


def _pad_to(x: Array, axis: int, mult: int, value: float = 0.0) -> Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _kernel(es_ref, ef_ref, mask_ref, df_ref, cov_ref, v_ref, wc_ref,
            ctx_ref, attn_ref, *, use_coverage: bool):
    """One batch row: es/ef [1, T, D]; mask/cov/attn [1, T, 1];
    df/ctx [1, 1, D]; v/wc [1, D].

    Shapes are chosen for the Mosaic TPU block-mapping rule: every block's
    trailing two dims are either (8, 128)-aligned or span the whole array
    dim, so per-row [T, 1] columns and [1, D] rows are legal while plain
    [1, T] per-row slices of a [B, T] array are not.
    """
    # es/ef arrive in their HBM dtype (bf16 under compute_dtype=bfloat16
    # — casting to f32 OUTSIDE the kernel would materialize full-width
    # copies in HBM and forfeit the bf16 bandwidth win); upcast here, in
    # VMEM, so the energy/softmax math is f32 regardless
    ef = ef_ref[0].astype(jnp.float32)   # [T, D]
    feats = ef + df_ref[0]               # + [1, D]
    if use_coverage:
        feats = feats + cov_ref[0] * wc_ref[...]   # [T, 1] * [1, D]
    e = jnp.sum(v_ref[...] * jnp.tanh(feats), axis=-1,
                keepdims=True)           # [T, 1]
    mask = mask_ref[0]                   # [T, 1]
    e = jnp.where(mask > 0, e, NEG)
    m = jnp.max(e)
    p = jnp.where(mask > 0, jnp.exp(e - m), 0.0)
    l = jnp.sum(p)
    # fully-masked row (empty streamed article): l=0 would give NaN via
    # 0/0 and poison p_gen/final_dist; clamp -> zero attention instead
    a = p / jnp.maximum(l, 1e-30)        # [T, 1]
    attn_ref[0] = a
    # context: aᵀ[1, T] @ es [T, D] on the MXU (contraction over dim 0);
    # HIGHEST precision keeps full f32 (the matvec is a sliver of the
    # kernel's work; default bf16 passes cost ~1e-2 absolute ctx error)
    ctx_ref[0] = jax.lax.dot_general(
        a, es_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _attention_xla(enc_states, enc_feats, enc_mask, dec_feats, coverage,
                   v, w_c, use_coverage):
    """Reference formula (ops/attention.py semantics) — backward path and
    non-TPU fallback."""
    feats = enc_feats + dec_feats[:, None, :]
    if use_coverage:
        feats = feats + coverage[:, :, None] * w_c[None, None, :]
    e = jnp.sum(v * jnp.tanh(feats), axis=-1)
    e = jnp.where(enc_mask > 0, e, NEG)
    e = e - jax.lax.stop_gradient(jnp.max(e, axis=-1, keepdims=True))
    p = jnp.exp(e) * (enc_mask > 0)
    # fully-masked row: clamp the l=0 denominator (match the kernels)
    attn = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    context = jnp.einsum("bt,btd->bd", attn, enc_states)
    return context, attn


def _attention_xla_shared(enc_states, enc_feats, enc_mask, dec_feats,
                          coverage, v, w_c, use_coverage):
    """The reference formula with the per-article encoder tensors SHARED
    across the K query rows (decode byte diet, ISSUE 7): enc_states /
    enc_feats are [T, D] and enc_mask [T] — no query axis — so the beam's
    K hypotheses broadcast against ONE copy and the context reduction is
    a plain [K, T] @ [T, D] matmul that streams the encoder from HBM
    once per step instead of K times.  dec_feats: [K, D]; coverage:
    [K, T].  Same math as _attention_xla row for row."""
    feats = enc_feats[None, :, :] + dec_feats[:, None, :]
    if use_coverage:
        feats = feats + coverage[:, :, None] * w_c[None, None, :]
    e = jnp.sum(v * jnp.tanh(feats), axis=-1)  # [K, T]
    e = jnp.where(enc_mask[None, :] > 0, e, NEG)
    e = e - jax.lax.stop_gradient(jnp.max(e, axis=-1, keepdims=True))
    p = jnp.exp(e) * (enc_mask[None, :] > 0)
    # fully-masked row: clamp the l=0 denominator (match the kernels)
    attn = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    context = attn @ enc_states  # promotes bf16 enc to f32 like the einsum
    return context, attn


def fused_attention_shared(enc_states, enc_feats, enc_mask, dec_feats,
                           coverage, v, w_c, use_coverage):
    """fused_attention for the shared-encoder decode layout (enc leaves
    carry no query axis; see _attention_xla_shared).  Forward-only — the
    beam search never differentiates through it.  TS_PALLAS=on keeps its
    meaning by broadcasting the encoder back to [K, ...] for the kernel
    (the kernel's grid is per query row); the default XLA path never
    materializes that broadcast."""
    if _use_pallas():
        K = dec_feats.shape[0]
        bc = lambda x: jnp.broadcast_to(x[None], (K,) + x.shape)  # noqa: E731
        return fused_attention(bc(enc_states), bc(enc_feats), bc(enc_mask),
                               dec_feats, coverage, v, w_c, use_coverage)
    return _attention_xla_shared(enc_states, enc_feats, enc_mask, dec_feats,
                                 coverage, v, w_c, use_coverage)


def _attention_pallas(enc_states, enc_feats, enc_mask, dec_feats, coverage,
                      v, w_c, use_coverage, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, D = enc_states.shape
    es = _pad_to(_pad_to(enc_states, 1, _LANE), 2, _LANE)
    ef = _pad_to(_pad_to(enc_feats, 1, _LANE), 2, _LANE)
    mask = _pad_to(enc_mask, 1, _LANE)
    cov = _pad_to(coverage, 1, _LANE)
    df = _pad_to(dec_feats, 1, _LANE)
    vp = _pad_to(v[None, :], 1, _LANE)[0]
    wcp = _pad_to(w_c[None, :], 1, _LANE)[0]
    Tp, Dp = es.shape[1], es.shape[2]

    row3 = lambda b: (b, 0, 0)
    rep = lambda b: (0, 0)
    ctx, attn = pl.pallas_call(
        functools.partial(_kernel, use_coverage=use_coverage),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Tp, Dp), row3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Tp, Dp), row3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Tp, 1), row3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, Dp), row3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Tp, 1), row3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Dp), rep, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Dp), rep, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Dp), row3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Tp, 1), row3, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, Dp), jnp.float32),
            jax.ShapeDtypeStruct((B, Tp, 1), jnp.float32),
        ],
        interpret=interpret,
        # es/ef keep their HBM dtype (bf16 mode streams half the bytes);
        # the kernel upcasts in VMEM
    )(es, ef,
      mask.astype(jnp.float32)[:, :, None], df.astype(jnp.float32)[:, None, :],
      cov.astype(jnp.float32)[:, :, None], vp[None].astype(jnp.float32),
      wcp[None].astype(jnp.float32))
    return ctx[:, 0, :D], attn[:, :T, 0]


def _blocked_kernel(es_ref, ef_ref, mask_ref, df_ref, cov_ref, v_ref, wc_ref,
                    ctx_ref, e_ref, m_scr, l_scr, ctx_scr,
                    *, use_coverage: bool):
    """Flash-style online-softmax block: grid (B, nT), T-blocks sequential.

    The context accumulates in VMEM scratch with the usual running-max
    rescaling and is normalized in-kernel at the last block.  The masked
    energies stream out per block ([Tb, 1] columns); the wrapper recovers
    the attention distribution from them with one cheap XLA softmax —
    that keeps every output block TPU-legal (no per-block scalar stores)
    while the [T, D] feats intermediate still never leaves VMEM.
    """
    import jax.experimental.pallas as pl

    j = pl.program_id(1)
    nT = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[0, 0] = NEG
        l_scr[0, 0] = 0.0
        ctx_scr[:, :] = jnp.zeros_like(ctx_scr)

    # upcast in VMEM (see _kernel): es/ef stream HBM->VMEM at their
    # native width, possibly bf16
    ef = ef_ref[0].astype(jnp.float32)   # [Tb, D]
    feats = ef + df_ref[0]               # + [1, D]
    if use_coverage:
        feats = feats + cov_ref[0] * wc_ref[...]   # [Tb, 1] * [1, D]
    e = jnp.sum(v_ref[...] * jnp.tanh(feats), axis=-1,
                keepdims=True)           # [Tb, 1]
    mask = mask_ref[0]                   # [Tb, 1]
    e = jnp.where(mask > 0, e, NEG)
    e_ref[0] = e

    m_old = m_scr[0, 0]
    m_new = jnp.maximum(m_old, jnp.max(e))
    scale = jnp.exp(m_old - m_new)
    p = jnp.where(mask > 0, jnp.exp(e - m_new), 0.0)   # [Tb, 1]
    l_scr[0, 0] = l_scr[0, 0] * scale + jnp.sum(p)
    ctx_scr[:, :] = ctx_scr[:, :] * scale + jax.lax.dot_general(
        p, es_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    m_scr[0, 0] = m_new

    @pl.when(j == nT - 1)
    def _finish():
        # clamp like the simple kernel: fully-masked row has l=0
        ctx_ref[0] = ctx_scr[:, :] / jnp.maximum(l_scr[0, 0], 1e-30)


def _attention_pallas_blocked(enc_states, enc_feats, enc_mask, dec_feats,
                              coverage, v, w_c, use_coverage,
                              block_t: int = 512, interpret=False):
    """Long-context path: stream T in `block_t` slices (VMEM holds one
    [block_t, D] slice at a time), online softmax across blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, D = enc_states.shape
    es = _pad_to(_pad_to(enc_states, 1, block_t), 2, _LANE)
    ef = _pad_to(_pad_to(enc_feats, 1, block_t), 2, _LANE)
    mask = _pad_to(enc_mask, 1, block_t)
    cov = _pad_to(coverage, 1, block_t)
    df = _pad_to(dec_feats, 1, _LANE)
    vp = _pad_to(v[None, :], 1, _LANE)
    wcp = _pad_to(w_c[None, :], 1, _LANE)
    Tp, Dp = es.shape[1], es.shape[2]
    nT = Tp // block_t

    brow3 = lambda b, j: (b, 0, 0)
    tb3 = lambda b, j: (b, j, 0)
    rep = lambda b, j: (0, 0)
    ctx, energies = pl.pallas_call(
        functools.partial(_blocked_kernel, use_coverage=use_coverage),
        grid=(B, nT),
        in_specs=[
            pl.BlockSpec((1, block_t, Dp), tb3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_t, Dp), tb3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_t, 1), tb3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, Dp), brow3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_t, 1), tb3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Dp), rep, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Dp), rep, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Dp), brow3, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_t, 1), tb3, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, Dp), jnp.float32),
            jax.ShapeDtypeStruct((B, Tp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.SMEM((1, 1), jnp.float32),
            pltpu.SMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, Dp), jnp.float32),
        ],
        interpret=interpret,
        # es/ef in their HBM dtype; in-kernel upcast (see _kernel)
    )(es, ef,
      mask.astype(jnp.float32)[:, :, None], df.astype(jnp.float32)[:, None, :],
      cov.astype(jnp.float32)[:, :, None], vp.astype(jnp.float32),
      wcp.astype(jnp.float32))
    # attention from the streamed energies: one cheap [B, Tp] softmax in
    # XLA (masked positions carry NEG so they exp to 0); the clamp keeps a
    # fully-masked row at zero attention instead of NaN
    e = energies[:, :, 0]
    m_fin = jnp.max(e, axis=-1, keepdims=True)
    p = jnp.where(mask > 0, jnp.exp(e - m_fin), 0.0)
    attn = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return ctx[:, 0, :D], attn[:, :T]


# VMEM budget heuristic: two [T, D] f32 slices per row beyond this, stream
# T in blocks instead (simple kernel holds both enc tensors at once).
_SIMPLE_KERNEL_MAX_ELEMS = 1_000_000


def _use_pallas() -> bool:
    """auto (default) prefers the XLA formula: on-device A/B at both
    reference scale (B16 T400 D512) and long context (B4 T4096 D512)
    measured the Pallas kernels at 0.99x / 0.94x of XLA on TPU v5e
    (a 2026-07 run on code older than today's; PERF.md Findings) —
    XLA's own fusion of this
    additive-attention chain is already near-roofline, so the kernels
    stay opt-in (TS_PALLAS=on) and serve the VMEM-constrained sp path
    (blocked variant) rather than the default train step."""
    env = os.environ.get("TS_PALLAS", "auto").lower()
    if env in ("0", "off", "false"):
        return False
    if env in ("1", "on", "true"):
        return True
    return False


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def fused_attention(enc_states: Array, enc_feats: Array, enc_mask: Array,
                    dec_feats: Array, coverage: Array, v: Array, w_c: Array,
                    use_coverage: bool) -> Tuple[Array, Array]:
    """(context [B, D], attn_dist [B, T]).  coverage is read only when
    use_coverage (pass zeros otherwise)."""
    if _use_pallas():
        T, D = enc_states.shape[1], enc_states.shape[2]
        if T * D > _SIMPLE_KERNEL_MAX_ELEMS:  # long-context: stream T
            return _attention_pallas_blocked(enc_states, enc_feats, enc_mask,
                                             dec_feats, coverage, v, w_c,
                                             use_coverage)
        return _attention_pallas(enc_states, enc_feats, enc_mask, dec_feats,
                                 coverage, v, w_c, use_coverage)
    return _attention_xla(enc_states, enc_feats, enc_mask, dec_feats,
                          coverage, v, w_c, use_coverage)


def _fwd(enc_states, enc_feats, enc_mask, dec_feats, coverage, v, w_c,
         use_coverage):
    out = fused_attention(enc_states, enc_feats, enc_mask, dec_feats,
                          coverage, v, w_c, use_coverage)
    return out, (enc_states, enc_feats, enc_mask, dec_feats, coverage, v, w_c)


def _bwd(use_coverage, saved, grads):
    """Backward = autodiff of the reference formula, recomputed (a
    rematerialization: forward-kernel speed, exact reference gradients)."""
    enc_states, enc_feats, enc_mask, dec_feats, coverage, v, w_c = saved
    _, vjp = jax.vjp(
        lambda es, ef, df, cov, vv, wc: _attention_xla(
            es, ef, enc_mask, df, cov, vv, wc, use_coverage),
        enc_states, enc_feats, dec_feats, coverage, v, w_c)
    d_es, d_ef, d_df, d_cov, d_v, d_wc = vjp(grads)
    return (d_es, d_ef, None, d_df, d_cov, d_v, d_wc)


fused_attention.defvjp(_fwd, _bwd)
