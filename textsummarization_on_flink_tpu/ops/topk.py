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
"""

from typing import Tuple

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


def _select(x: Array, k: int) -> Tuple[Array, Array]:
    n = x.shape[-1]
    axis = x.ndim - 1
    idx = lax.broadcasted_iota(jnp.int32, x.shape, axis)
    # a spent element reads (-inf, n): it loses to every live one, a
    # live -inf included (its index is under n), and k <= n leaves one
    spent = (jnp.array(-jnp.inf, x.dtype), jnp.int32(n))
    vals, ids = [], []
    xv, xi = x, idx
    for j in range(k):
        if j:  # what comes after the last pick, in top_k's order
            pv, pi = vals[-1][..., None], ids[-1][..., None]
            live = (x < pv) | ((x == pv) & (idx > pi))
            xv = jnp.where(live, x, spent[0])
            xi = jnp.where(live, idx, spent[1])
        v, i = lax.reduce((xv, xi), spent, _first, (axis,))
        vals.append(v)
        ids.append(i)
    return jnp.stack(vals, axis=-1), jnp.stack(ids, axis=-1)


def top_k(x: Array, k: int) -> Tuple[Array, Array]:
    """``jax.lax.top_k(x, k)``, by selection where ``_plan`` says the
    row is long enough for it to win (module docstring)."""
    n = x.shape[-1]
    if (not 0 < k <= n or not jnp.issubdtype(x.dtype, jnp.floating)
            or _plan(n, k) == "lax"):
        return lax.top_k(x, k)
    return _select(x, k)
