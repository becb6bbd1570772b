"""Exact top-k over the last axis by selection, not by sorting the row.

``top_k(x, k)`` has the contract of ``jax.lax.top_k``: the k largest
values of every row, descending, equal values by ascending index, the
values bit-equal to the row's own entries, int32 indices.  The beam
step keeps 2 x beam = 8 of a 50 128-wide row.  XLA:TPU has a native
TopK for a rank-2 operand only; every decode path here `vmap`s the
per-article step, the operand arrives with rank 3, and ``lax.top_k``
becomes a full sort of each row — 67.7 ms at [256, 4, 50 128] float32
on one v5e (my chip run, PR 26), 86% of the slot step (PERF.md).

The selection is k passes: pass j takes, in ONE variadic reduce, the
first element of the row in (value descending, index ascending) order
among those that come after pass j-1's pick.  No sort, no gather, no
reshape, nothing to tune; k reads of the row, 2.25 ms at the shape
above where k reads at the chip's 819 GB/s are 2.0 ms.  The other
exact form tried (contiguous bin maxima, ``lax.top_k`` over them,
gather of k bins and re-rank) took 4.2 ms there: its small sorts are
rank 3 too.  Timings and the choice: PERF.md section 6, "PR 26".

Where it agrees with ``lax.top_k``: every floating row without NaN
(``lax.top_k`` sorts in a total order, NaN first and +0.0 before -0.0;
here a NaN is never picked and the two zeros are a tie, broken by
index).  The decode step's rows are a probability mixture: no NaN, no
-0.0.

``mixture_top_k`` is ``top_k`` of the pointer mixture, vocab_dist being
the softmax of the step's vocabulary scores,

    final[w] = p_gen * vocab_dist[w] + (1 - p_gen) * sum_{t: id_t = w} attn[t]

without the mixture: copy mass only raises a word, so a word of the
true top k is one of the article's own ids or one of the top k of
``p_gen * vocab_dist`` alone (outside both, k words already stand at or
above it, and before it where they tie).  At most k + T_enc candidates
a row are ranked as (value, id) pairs, and the extended row
[.., V + OOV buckets] — its zero fill, pad, scatter-add and the copies
around them, half of the slot step before ISSUE 31 — is never built.
``extended_mixture`` builds it: the definition, and the path for a
vocabulary too short for the candidates to win (``_mixture_plan``).

An article word's vocabulary share needs its score, and a gather of
102 400 of them from the [256, 4, 50 000] block was 2.0 ms of every
step with 0.6 more for the relayout XLA makes for it (XLA:TPU gathers
at 18-19 ns an index whatever the form; PERF.md section 6, "PR 33").
The ids do not change while an article decodes, so a search gathers the
output head's COLUMNS at them once before its loop (``head_at``), its
step projects onto those beside the row (``output . W[:, w] + v[w]``,
the same operands through the same matmul), and ``mixture_top_k`` takes
the scores from the caller.  A caller with no loop passes none and the
scores are gathered from the row.
"""

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

#: rows shorter than this go to lax.top_k: 512 is the shortest row the
#: two were timed at (vmapped [256, 4, 512], k = 8: the sort 1.04 ms,
#: selection 0.42 ms; my chip run, PR 26), and the tests' vocabularies
#: of a few hundred stay on the stock lowering
MIN_ROW = 512
#: one pass a pick, unrolled: cost is linear in k, and 16 (beam 8) is
#: as far as the k = 8 timing is stretched (at 512 the sort's 1.04 ms
#: buys about 20 passes)
MAX_PASSES = 16
#: the candidates rank instead of the extended row only where they are
#: at most this share of the vocabulary's row.  Timed at ONE shape,
#: [256, 4, 50 000] with 400 article positions (candidates 0.8% of the
#: row: 9.87 ms the softmax, the row and its selection, 4.97 the
#: candidates; my chip run, PR 31); the work beside the vocabulary's
#: selection grows with T_enc squared (the equal-id mask), so the line
#: is drawn far from where it could cost, and the tests' vocabularies
#: stay dense
MAX_CANDIDATE_SHARE = 1 / 8
#: the id of a candidate that is out of the ranking
_NO_ID = jnp.iinfo(jnp.int32).max


def _plan(n: int, k: int) -> str:
    """``"select"`` or ``"lax"`` for a row of length n and k picks:
    all that is known at trace time (under `vmap` the leading axes are
    not), and so all the choice may rest on."""
    return "select" if n >= MIN_ROW and k <= MAX_PASSES else "lax"


def _first(a, b):
    """Of two (value, index) pairs, the one ``top_k`` lists first."""
    (av, ai), (bv, bi) = a, b
    a_first = (av > bv) | ((av == bv) & (ai < bi))
    return jnp.where(a_first, av, bv), jnp.where(a_first, ai, bi)


def _select(x: Array, k: int, ids: Optional[Array] = None,
            ) -> Tuple[Array, Array]:
    """The k first of the last axis in (value descending, id ascending)
    order.  ``ids`` None: an element's id is its index.  ``ids`` given
    (int32, x's shape, under ``_NO_ID``): elements equal in value AND
    id are one element, picked once."""
    axis = x.ndim - 1
    if ids is None:
        idx = lax.broadcasted_iota(jnp.int32, x.shape, axis)
        beyond = x.shape[-1]
    else:
        idx, beyond = ids, _NO_ID
    # a spent element reads (-inf, beyond): it loses to every live one,
    # a live -inf included (its id is under that), and k <= n leaves one
    spent = (jnp.array(-jnp.inf, x.dtype), jnp.int32(beyond))
    vals, picks = [], []
    xv, xi = x, idx
    for j in range(k):
        if j:  # what comes after the last pick, in top_k's order
            pv, pi = vals[-1][..., None], picks[-1][..., None]
            live = (x < pv) | ((x == pv) & (idx > pi))
            xv = jnp.where(live, x, spent[0])
            xi = jnp.where(live, idx, spent[1])
        v, i = lax.reduce((xv, xi), spent, _first, (axis,))
        vals.append(v)
        picks.append(i)
    return jnp.stack(vals, axis=-1), jnp.stack(picks, axis=-1)


def top_k(x: Array, k: int) -> Tuple[Array, Array]:
    """``jax.lax.top_k(x, k)``, by selection where ``_plan`` says the
    row is long enough for it to win (module docstring)."""
    n = x.shape[-1]
    if (not 0 < k <= n or not jnp.issubdtype(x.dtype, jnp.floating)
            or _plan(n, k) == "lax"):
        return lax.top_k(x, k)
    return _select(x, k)


def _mixture_plan(v: int, t_enc: int, k: int) -> str:
    """``"candidates"`` or ``"dense"`` for a vocabulary row of length
    v, t_enc article positions and k picks: the two shapes the trace
    can see, as ``_plan`` chooses."""
    if _plan(v, k) == "select" and (k + t_enc) <= v * MAX_CANDIDATE_SHARE:
        return "candidates"
    return "dense"


class ArticleHead(NamedTuple):
    """The output head at an article's ids (``head_at``)."""

    w: Array  # [.., T_enc, H]: the projection's columns, one a row
    v: Array  # [.., T_enc]: the bias


def _in_row(ext_ids: Array, v: int) -> Array:
    """An article's ids as indices into the vocabulary's row: an OOV
    id reads place 0, and ``_mixture_candidates`` masks what it read."""
    return jnp.where(ext_ids < v, ext_ids, 0)


def head_at(w: Array, v: Array, ext_ids: Array, k: int,
            ) -> Optional[ArticleHead]:
    """The columns of the output head ``scores = x @ w + v`` (w [H, V],
    v [V]) at the articles' ids [.., T_enc], for a step that ranks k:
    loop-invariant, so a search gathers them once and its step gives
    ``mixture_top_k`` the article-side scores ``x @ head.w.T + head.v``.
    None where ``_mixture_plan`` builds the row, which reads no score
    by id.  Rows of the transposed matrix: 1 KB an index."""
    if k <= 0 or _mixture_plan(w.shape[-1], ext_ids.shape[-1],
                               k) != "candidates":
        return None
    at = _in_row(ext_ids, w.shape[-1])
    with jax.named_scope("vocab_dist"):
        return ArticleHead(w=w.T[at], v=v[at])


def extended_mixture(vocab_dist: Array, attn_dist: Array, p_gen: Array,
                     ext_ids: Array, ext_size: int) -> Array:
    """The pointer mixture over the extended vocabulary [B, ext_size]
    (model.py:146-183): ``p_gen * vocab_dist`` in the first V places and
    the copy mass ``(1 - p_gen) * attn_dist`` scatter-added at the
    article's extended ids.  vocab_dist [B, V], attn_dist [B, T_enc],
    p_gen [B], ext_ids [B, T_enc] or, shared by the rows, [T_enc]."""
    B, V = vocab_dist.shape
    ext_ids = jnp.broadcast_to(ext_ids, attn_dist.shape)
    weighted_vocab = p_gen[:, None] * vocab_dist
    weighted_attn = (1.0 - p_gen)[:, None] * attn_dist  # [B, T_enc]
    base = jnp.zeros((B, ext_size), vocab_dist.dtype)
    base = base.at[:, :V].set(weighted_vocab)
    b_idx = jnp.arange(B)[:, None].repeat(attn_dist.shape[1], axis=1)
    return base.at[b_idx, ext_ids].add(weighted_attn)


def _mixture_candidates(vocab_scores: Array, attn_dist: Array, p_gen: Array,
                        ext_ids: Array, k: int, ext_size: int,
                        art_scores: Optional[Array] = None,
                        ) -> Tuple[Array, Array]:
    V = vocab_scores.shape[-1]
    dtype = vocab_scores.dtype
    out = jnp.array(-jnp.inf, dtype)
    # (under mixture_top_k's ``vocab_dist`` scope, all but the selection)
    # jax.nn.softmax's own arithmetic, with its two row statistics kept:
    # a word's probability is exp(score - top) / mass wherever it is
    # computed, so the article's few are made from their SCORES (the
    # caller's, or gathered here) and no normalised row is written out
    top = jnp.max(vocab_scores, axis=-1, keepdims=True)
    unnormalized = jnp.exp(vocab_scores - top)
    mass = jnp.sum(unnormalized, axis=-1, keepdims=True)
    with jax.named_scope("topk"):  # the one selection over the vocabulary
        voc_v, voc_i = top_k(
            (p_gen[:, None] * (unnormalized / mass)).astype(dtype), k)
    # the article's side: every position worth the vocabulary's
    # share of its id plus the copy mass of ALL that id's positions.
    # An id's positions are then the same (value, id) pair — the
    # same sum over the same masked row — and _select passes over a
    # pair once, so no position need be dropped as a repeat
    copy = ((1.0 - p_gen)[:, None] * attn_dist).astype(dtype)
    same = ext_ids[..., :, None] == ext_ids[..., None, :]  # [.., T, T]
    copy = jnp.sum(jnp.where(same, copy[:, None, :], 0), axis=-1)
    in_vocab = ext_ids < V
    if art_scores is not None:  # the caller projected onto head_at's
        base = art_scores
    elif ext_ids.ndim == 1:  # one article under all rows: one gather
        base = jnp.take(vocab_scores, _in_row(ext_ids, V), axis=-1)
    else:
        base = jnp.take_along_axis(vocab_scores, _in_row(ext_ids, V), -1)
    base = (p_gen[:, None] * (jnp.exp(base - top) / mass)).astype(dtype)
    base = jnp.where(in_vocab, base, 0)
    # an id past the extended row has no place in it (the scatter
    # drops it); a vocabulary pick the article holds ranks there
    art_ok = jnp.broadcast_to(ext_ids < ext_size, base.shape)
    art_i = jnp.broadcast_to(ext_ids, base.shape)
    held = jnp.any(voc_i[:, :, None] == art_i[:, None, :], axis=-1)
    vals = jnp.concatenate([jnp.where(held, out, voc_v),
                            jnp.where(art_ok, base + copy, out)], -1)
    ids = jnp.concatenate([jnp.where(held, _NO_ID, voc_i),
                           jnp.where(art_ok, art_i, _NO_ID)], -1)
    return _select(vals, k, ids)


def mixture_top_k(vocab_scores: Array, attn_dist: Array, p_gen: Array,
                  ext_ids: Array, k: int, ext_size: int,
                  art_scores: Optional[Array] = None,
                  ) -> Tuple[Array, Array]:
    """``top_k(extended_mixture(softmax(vocab_scores), attn_dist, p_gen,
    ext_ids, ext_size), k)``: the same ids in the same order, the values
    equal up to the order in which one id's copy mass is summed (which
    the scatter-add leaves open too), and up to the float32 accumulation
    order of an article word's logit, when the caller supplies it — from
    the candidates where ``_mixture_plan`` says so (module docstring).
    vocab_scores [B, V] are the output projection's, before the
    softmax; the other shapes as ``extended_mixture``'s.  art_scores
    [B, T_enc], optional: the same projection's scores at the article's
    ids, from ``head_at``'s columns (an OOV id's place is masked here,
    and the row's own top and mass normalise them); without them they
    are gathered from the row.  The
    vocabulary-wide selection carries the ``topk`` named scope and the
    rest the ``vocab_dist`` scope, whichever path is taken."""
    V, T = vocab_scores.shape[-1], attn_dist.shape[-1]
    with jax.named_scope("vocab_dist"):
        if k > 0 and _mixture_plan(V, T, k) == "candidates":
            return _mixture_candidates(vocab_scores, attn_dist, p_gen,
                                       ext_ids, k, ext_size, art_scores)
        row = extended_mixture(jax.nn.softmax(vocab_scores, axis=-1),
                               attn_dist, p_gen, ext_ids, ext_size)
        with jax.named_scope("topk"):
            return top_k(row, k)
