"""Ring attention: sequence-parallel self-attention over the sp mesh axis.

The reference has NO long-context story — inputs are truncated to
``max_enc_steps`` (/root/reference/src/main/python/pointer-generator/
batcher.py:52-55).  This module is the rebuild's first-class sequence/
context parallelism (SURVEY §5.7): each sp shard holds its own block of
queries, keys, and values ([B, T/sp, ...]); K/V blocks rotate around the
ring via ``jax.lax.ppermute`` while a numerically-stable online softmax
accumulates the output — the full [T, T] score matrix never exists on any
one device, and per-step communication is the [B, T/sp, nh, hd] K/V
blocks riding ICI neighbor-to-neighbor (the ring pattern overlaps compute
with transfer on TPU).

Semantically identical to full masked softmax attention: the online
max/sum telescopes to the global softmax (flash-attention algebra), and
padding keys are masked with -1e30 before the max so a block of pure
padding contributes exp(-1e30 - m) = 0.

Used by the transformer family (models/transformer.py) when
``hps.sp_attention`` selects 'ring' (or 'ulysses', below) and the encoder
runs under an sp>1 mesh; exposed standalone for tests and reuse.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array

NEG = -1e30


def _block_attn(q: Array, k: Array, kmask: Array,
                sm_scale: float) -> Array:
    """Masked scores of local q against one K block.

    q: [B, Tq, nh, hd]; k: [B, Tk, nh, hd]; kmask: [B, Tk].
    Returns logits [B, nh, Tq, Tk] (f32, padding keys at -1e30).
    """
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k).astype(jnp.float32)
    logits = logits * sm_scale
    logits = jnp.where(kmask[:, None, None, :] > 0, logits, NEG)
    return logits


def ring_self_attention(q: Array, k: Array, v: Array, kv_mask: Array,
                        axis_name: str, sm_scale: float) -> Array:
    """One shard's view: q/k/v [B, T_blk, nh, hd], kv_mask [B, T_blk].

    Must run inside shard_map (or any SPMD context) where `axis_name` is a
    ring of sp devices.  Returns the attention output [B, T_blk, nh, hd]
    for the local queries against the GLOBAL key/value sequence.
    """
    n = jax.lax.axis_size(axis_name)
    B, Tb, nh, hd = q.shape
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(carry, rotate):
        m, l, o, k_cur, v_cur, mask_cur = carry
        logits = _block_attn(q, k_cur, mask_cur, sm_scale)
        m_blk = jnp.max(logits, axis=-1)  # [B, nh, Tq]
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(logits - m_new[..., None])  # [B, nh, Tq, Tk]
        p = p * (mask_cur[:, None, None, :] > 0)  # exact zeros on padding
        scale_old = jnp.exp(m - m_new)
        l = l * scale_old + jnp.sum(p, axis=-1)
        o = o * scale_old[..., None] + jnp.einsum(
            "bnqk,bknd->bnqd", p.astype(v_cur.dtype), v_cur
        ).astype(jnp.float32)
        if rotate:
            # rotate K/V/mask to the next device on the ring (neighbor
            # transfer over ICI; overlapped with the next block's compute)
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            mask_cur = jax.lax.ppermute(mask_cur, axis_name, perm)
        return m_new, l, o, k_cur, v_cur, mask_cur

    m0 = jnp.full((B, nh, Tb), NEG, jnp.float32)
    l0 = jnp.zeros((B, nh, Tb), jnp.float32)
    o0 = jnp.zeros((B, nh, Tb, hd), jnp.float32)
    carry = (m0, l0, o0, k, v, kv_mask)
    # python loop (n is small and static) keeps each ppermute a separate
    # XLA op that the scheduler can overlap with the matmuls; the last
    # block's rotation is skipped — its carry is never read
    for i in range(n):
        carry = body(carry, rotate=i < n - 1)
    _, l, o, _, _, _ = carry
    # fully-masked query rows (all-padding article): l=0 -> zero output
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B,Tq,nh,hd]


def make_ring_attention(mesh: Mesh, axis_name: str = "sp"):
    """shard_map-wrapped ring attention over `mesh`'s sp axis.

    Inputs are GLOBAL arrays (inside or outside jit): q/k/v
    [B, T, nh, hd] sharded (or shardable) as P(None, sp) on T; mask
    [B, T].  Output matches q's global shape.
    """
    return make_sp_attention(mesh, "ring", axis_name)


# --------------------------------------------------------------------------
# Ulysses (all-to-all) sequence parallelism — the other standard SP layout
# --------------------------------------------------------------------------

def ulysses_self_attention(q: Array, k: Array, v: Array, kv_mask: Array,
                           axis_name: str, sm_scale: float) -> Array:
    """DeepSpeed-Ulysses-style SP: all-to-all re-shards q/k/v from
    sequence-sharded [B, T/sp, nh, hd] to head-sharded [B, T, nh/sp, hd],
    runs ordinary full attention per local head group, and all-to-alls
    back.  Per call: three input all-to-alls (q/k/v) + one mask
    all-gather + one output all-to-all, vs the ring's (sp-1) K/V
    rotations — better when heads divide evenly and T is only moderately
    long; the ring wins when T is so long that even one device's full-T
    K/V working set is the constraint.  Requires nh % sp == 0."""
    # [B, Tb, nh, hd] -> [B, T, nh/sp, hd]: split heads, concat sequence
    qh = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    kh = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    vh = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    mask_full = jax.lax.all_gather(kv_mask, axis_name, axis=1, tiled=True)
    logits = _block_attn(qh, kh, mask_full, sm_scale)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = probs * (mask_full[:, None, None, :] > 0)  # all-padding row -> 0
    ctx = jnp.einsum("bnqk,bknd->bqnd", probs.astype(vh.dtype), vh)
    # [B, T, nh/sp, hd] -> [B, T/sp, nh, hd]
    return jax.lax.all_to_all(ctx, axis_name, split_axis=1, concat_axis=2,
                              tiled=True).astype(q.dtype)


def make_sp_attention(mesh: Mesh, mode: str, axis_name: str = "sp"):
    """shard_map-wrapped sequence-parallel attention over `mesh`'s sp
    axis.  mode: 'ring' or 'ulysses'.  Global-array calling convention is
    identical for both (q/k/v [B, T, nh, hd] T-sharded, mask [B, T])."""
    if mode == "ring":
        inner = ring_self_attention
    elif mode == "ulysses":
        inner = ulysses_self_attention
    else:
        raise ValueError(f"unknown sp_attention mode {mode!r}")

    def fn(q, k, v, mask, sm_scale):
        return inner(q, k, v, mask, axis_name, sm_scale)

    batch = "dp" if mesh.shape.get("dp", 1) > 1 else None
    spec4 = P(batch, axis_name, None, None)
    spec2 = P(batch, axis_name)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(spec4, spec4, spec4, spec2, None),
                         out_specs=spec4, check_vma=False)


# --------------------------------------------------------------------------
# Mesh context: lets model code reach the ambient mesh during pjit tracing
# --------------------------------------------------------------------------

_CURRENT_MESH: Optional[Mesh] = None


class mesh_context:
    """Set the ambient mesh while tracing a sharded step so model-level
    code (transformer ring attention) can build shard_map calls against
    it.  Trace-time only: the mesh is captured into the jaxpr."""

    def __init__(self, mesh: Optional[Mesh]):
        self._mesh = mesh
        self._prev: Optional[Mesh] = None

    def __enter__(self):
        global _CURRENT_MESH
        self._prev = _CURRENT_MESH
        _CURRENT_MESH = self._mesh
        return self._mesh

    def __exit__(self, *exc):
        global _CURRENT_MESH
        _CURRENT_MESH = self._prev
        return False


def current_mesh() -> Optional[Mesh]:
    return _CURRENT_MESH
