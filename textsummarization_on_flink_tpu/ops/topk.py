"""Exact top-k over the last axis by selection, not by sorting the row.

``top_k(x, k)`` has the contract of ``jax.lax.top_k``: the k largest
values of every row, descending, equal values by ascending index, the
values bit-equal to the row's own entries, int32 indices.  The beam
step keeps 2 x beam = 8 of a 50 128-wide row.  XLA:TPU has a native
TopK for a rank-2 operand only; every decode path here `vmap`s the
per-article step, the operand arrives with rank 3, and ``lax.top_k``
becomes a full sort of each row — 67.7 ms at [256, 4, 50 128] float32
on one v5e (my chip run, PR 26), 86% of the slot step (PERF.md).

The selection is k / m passes: pass j takes, in ONE variadic reduce,
the m first elements of the row in (value descending, index ascending)
order among those that come after pass j-1's last pick; the reduce
carries m (value, index) pairs and its combiner merges two sorted
m-lists (``_merge``: a bitonic merge's half-cleaner and log2 m
compare-exchange stages; m = 1 is a plain "which comes first").  No
sort, no gather, no reshape, no pad.  A pass of one pick runs at the
memory roofline of one read of the row (0.254 ms where 205 MB at the
chip's 819 GB/s are 0.25), so one pick a pass was eight reads, 31% of
the slot step (PERF.md section 5, PR 36); two picks a pass still nearly
do (0.289 ms a pass in the cell), four or eight are bound by the vector
unit (0.58, 1.37).  ms a call, k = 8, the cell's producer ``p_gen *
exp(z - top) / mass`` fused into every pass, every form bit-equal to
``lax.top_k`` on the chip (my chip run, PR 37):

    shape                 m = 1    m = 2    m = 4    m = 8   lax.top_k
    [256, 4, 50 000]      2.236    1.193    1.206    1.406   69.4
    [64, 4, 50 000]       0.821    0.748    0.789    0.799   26.2
    [1 024, 50 128]       2.249    1.222    1.279    1.431   1.85
    [256, 4, 152 064]     8.292    8.786    8.848    9.679   404 (PR 26)

``_plan(n, k)`` takes m from those: ``PICKS_A_PASS`` = 2, never more
than cover k.  Two a pass loses 6-13% to one a pass where the row's
length is a multiple of 128 and 32 768 or more (at [256, 4, n], n =
32 768 / 65 536 / 131 072 / 152 064: m = 1 1.81 / 3.46 / 7.03 / 8.30
against m = 2 1.93 / 3.76 / 7.97 / 8.78) and wins everywhere else
(16 384 and 100 000: 1.01 / 4.41 against 0.97 / 2.79): a vocabulary
is seldom such a multiple, so there is no rule by length.  The
other exact form PR 26 tried (contiguous bin maxima, ``lax.top_k`` over
them, gather of k bins and re-rank) took 4.2 ms at the first shape: its
small sorts are rank 3 too.

A kernel that reads the row ONCE (it walks the row in index order and
keeps eight a sublane, 0.49 ms at the first shape) was built, was
bit-equal, and waits: PERF.md section 7.  Timings and the choice:
PERF.md section 6, "PR 26" and "PR 37".

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
#: the passes are unrolled and their cost is linear in k: 16 (beam 8)
#: is as far as the k = 8 timing is stretched (at 512 the sort's 1.04
#: ms buys about 20 single picks)
MAX_K = 16
#: picks a pass (a power of two; module docstring for the timings)
PICKS_A_PASS = 2
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


def _plan(n: int, k: int) -> int:
    """The picks a pass of the selection, for a row of length n and k
    picks, or 0 for ``lax.top_k``: all that is known at trace time
    (under `vmap` the leading axes are not), and so all the choice may
    rest on."""
    if n < MIN_ROW or k > MAX_K:
        return 0
    return min(PICKS_A_PASS, 1 << (k - 1).bit_length())


def _a_first(a, b):
    """Whether ``top_k`` lists the (value, index) pair a before b."""
    (av, ai), (bv, bi) = a, b
    return (av > bv) | ((av == bv) & (ai < bi))


def _first(a, b):
    """Of two (value, index) pairs, the one ``top_k`` lists first."""
    a_first = _a_first(a, b)
    return jnp.where(a_first, a[0], b[0]), jnp.where(a_first, a[1], b[1])


def _in_order(a, b):
    """Two (value, index) pairs as ``top_k`` lists them."""
    a_first = _a_first(a, b)
    return ((jnp.where(a_first, a[0], b[0]), jnp.where(a_first, a[1], b[1])),
            (jnp.where(a_first, b[0], a[0]), jnp.where(a_first, b[1], a[1])))


def _merge(a, b):
    """The m first of two lists of m pairs, each in ``top_k``'s order
    (m a power of two): the half-cleaner of a bitonic merge — place by
    place the first of a[j] and b[m - 1 - j] are the m first of the 2m,
    and bitonic — then the log2 m compare-exchange stages that order a
    bitonic list.  m = 1 is ``_first``."""
    m = len(a)
    c = [_first(a[j], b[m - 1 - j]) for j in range(m)]
    half = m // 2
    while half:
        for lo in range(m):
            if not lo & half:
                c[lo], c[lo + half] = _in_order(c[lo], c[lo + half])
        half //= 2
    return c


def _select(x: Array, k: int, ids: Optional[Array] = None, m: int = 1,
            ) -> Tuple[Array, Array]:
    """The k first of the last axis in (value descending, id ascending)
    order, m (a power of two) a pass.  ``ids`` None: an element's id is
    its index.  ``ids`` given (int32, x's shape, under ``_NO_ID``; m
    is 1): elements equal in value AND id are one element, picked
    once."""
    axis = x.ndim - 1
    if ids is None:
        idx = lax.broadcasted_iota(jnp.int32, x.shape, axis)
        beyond = x.shape[-1]
    else:
        idx, beyond = ids, _NO_ID
    # a spent element reads (-inf, beyond): it loses to every live one,
    # a live -inf included (its id is under that), and k <= n leaves one
    spent = (jnp.array(-jnp.inf, x.dtype), jnp.int32(beyond))
    # an element enters a pass as the list of itself and m - 1 spent
    fill = [(jnp.full(x.shape, s, s.dtype),) * (m - 1) if m > 1 else ()
            for s in spent]
    vals, picks = [], []
    xv, xi = x, idx
    for j in range(0, k, m):
        if j:  # what comes after the last pick, in top_k's order
            pv, pi = vals[-1][..., None], picks[-1][..., None]
            live = (x < pv) | ((x == pv) & (idx > pi))
            xv = jnp.where(live, x, spent[0])
            xi = jnp.where(live, idx, spent[1])
        out = lax.reduce(
            (xv,) + fill[0] + (xi,) + fill[1],
            (spent[0],) * m + (spent[1],) * m,
            lambda a, b: _unzip(_merge(_zip(a), _zip(b))), (axis,))
        vals.extend(out[:m])
        picks.extend(out[m:])
    return jnp.stack(vals[:k], axis=-1), jnp.stack(picks[:k], axis=-1)


def _zip(flat):
    """(v0 .. vm-1, i0 .. im-1), a variadic reduce's side, as pairs."""
    m = len(flat) // 2
    return list(zip(flat[:m], flat[m:]))


def _unzip(pairs):
    return tuple(v for v, _ in pairs) + tuple(i for _, i in pairs)


def top_k(x: Array, k: int) -> Tuple[Array, Array]:
    """``jax.lax.top_k(x, k)``, by selection where ``_plan`` says the
    row is long enough for it to win (module docstring)."""
    n = x.shape[-1]
    m = _plan(n, k) if 0 < k <= n else 0
    if not m or not jnp.issubdtype(x.dtype, jnp.floating):
        return lax.top_k(x, k)
    return _select(x, k, m=m)


def _mixture_plan(v: int, t_enc: int, k: int) -> str:
    """``"candidates"`` or ``"dense"`` for a vocabulary row of length
    v, t_enc article positions and k picks: the two shapes the trace
    can see, as ``_plan`` chooses."""
    if _plan(v, k) and (k + t_enc) <= v * MAX_CANDIDATE_SHARE:
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
