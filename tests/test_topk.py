"""ops.topk.top_k against jax.lax.top_k: values bit-equal, ids equal —
the contract the beam step's selection stands on (ISSUE 26) — and that
the selection and the candidate ranking (ISSUE 31; its contract:
tests/test_mixture_topk.py) ENGAGE where the benchmark's cell runs
them: the slot step lowered at pg_see2017's vocabulary holds no sort
and no top-k over a vocabulary-wide operand, no scatter into one, and
nothing 50 128 wide at all — the extended row is never built.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import __graft_entry__ as ge
from _hlo import (score_gathers, wide_dimensions, wide_gathers, wide_reduces,
                  wide_row_orderings, wide_scatters)
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.decode import beam_search
from textsummarization_on_flink_tpu.models import get_family
from textsummarization_on_flink_tpu.ops import topk

LENGTHS = (7, 64, 1000, 50000, 50128, 152064)
LEAD = (3, 2)


def _softmax(rng, n):
    z = rng.normal(size=LEAD + (n,)).astype(np.float32) * 3
    e = np.exp(z - z.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _zero_tail(rng, n):
    """The extended vocabulary's OOV buckets: 128 exact zeros at the
    end of a softmax (an article with no OOV word)."""
    x = _softmax(rng, n)
    x[..., -min(128, n - 1):] = 0.0
    return x


def _many_ties(rng, n):
    return rng.integers(0, 5, size=LEAD + (n,)).astype(np.float32)


def _tie_on_kth(rng, n):
    """k - 1 clear winners, then MORE equal values than places are
    left, far apart: the k-th place goes to the lowest index."""
    del rng
    x = np.zeros(LEAD + (n,), np.float32)
    x[..., n // 2] = 3.0  # the one clear winner at k = 2
    x[..., [n - 1, 3, n // 3, n - 2, 1, n // 2 + 1]] = 2.0
    x[..., [n - 3, 5, 2 * n // 3]] = 1.0
    return x


def _all_equal(rng, n):
    del rng
    return np.full(LEAD + (n,), 0.25, np.float32)


def _neg_inf(rng, n):
    """-inf entries, and rows with fewer finite values than k."""
    x = _softmax(rng, n)
    x[..., ::3] = -np.inf
    x[0] = -np.inf
    x[0, :, n // 2] = 1.0
    return x


ROWS = {"softmax": _softmax, "zero_tail": _zero_tail,
        "many_ties": _many_ties, "tie_on_kth": _tie_on_kth,
        "all_equal": _all_equal, "neg_inf": _neg_inf}


def _bits(a):
    return np.asarray(a.astype(jnp.float32)).view(np.uint32)


def _picks_a_pass(k):
    """Every m ``_select`` can take for k picks: the powers of two up
    to the first that covers k."""
    return [1 << e for e in range((k - 1).bit_length() + 1)]


#: ``top_k`` as ``_plan`` routes it (m None) at every length, and the
#: selection at each m a pass it can take, where a row is long enough
CASES = [(n, k, None) for n in LENGTHS for k in (2, 8) if k <= n] + [
    (n, k, m) for n in (1000, 50000, 152064) for k in (2, 8)
    for m in _picks_a_pass(k)]


@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n,k,m", CASES)
def test_top_k_is_lax_top_k(n, k, m, dtype, vmapped):
    rng = np.random.default_rng(n + k)
    if m is None:
        fn = lambda r: topk.top_k(r, k)  # noqa: E731
    else:
        fn = lambda r: topk._select(r, k, m=m)  # noqa: E731
    if vmapped:  # two leading axes, as the slot step (slots, then beam)
        fn = jax.vmap(jax.vmap(fn))
    fn = jax.jit(fn)
    for name, make in ROWS.items():
        x = jnp.asarray(make(rng, n)).astype(dtype)
        want_v, want_i = jax.lax.top_k(x, k)
        got_v, got_i = fn(x)
        assert got_v.dtype == want_v.dtype and got_i.dtype == want_i.dtype
        np.testing.assert_array_equal(_bits(got_v), _bits(want_v),
                                      err_msg=name)
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i),
                                      err_msg=name)


def test_a_pass_that_overshoots_k_drops_the_rest():
    """k = 6 (beam 3) at four a pass: two passes, the last two picks
    of the second dropped."""
    x = jnp.asarray(_many_ties(np.random.default_rng(6), 1000))
    want_v, want_i = jax.lax.top_k(x, 6)
    got_v, got_i = jax.jit(lambda r: topk._select(r, 6, m=4))(x)
    np.testing.assert_array_equal(_bits(got_v), _bits(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))


def test_plan_is_pinned():
    """The one choice, from the row's length and k alone: how many
    picks a pass of the selection takes at the cell's width (never
    more than cover k), and lax.top_k (0) at a test vocabulary's."""
    m = topk.PICKS_A_PASS
    assert m == 2
    assert topk._plan(50000, 8) == m
    assert topk._plan(50128, 8) == m
    assert topk._plan(152064, 8) == m
    assert topk._plan(50128, 2) == 2
    assert topk._plan(50128, 1) == 1
    assert topk._plan(64, 8) == 0
    assert topk._plan(50128, 64) == 0


def test_short_rows_and_integers_are_lax_top_k_itself():
    x = jnp.arange(24.0).reshape(2, 12)
    assert "top_k" in str(jax.make_jaxpr(lambda r: topk.top_k(r, 4))(x))
    big = jnp.zeros((2, 1024), jnp.int32)
    assert "top_k" in str(jax.make_jaxpr(lambda r: topk.top_k(r, 4))(big))
    assert "top_k" not in str(jax.make_jaxpr(
        lambda r: topk.top_k(r, 4))(big.astype(jnp.float32)))


# -- the mechanism engages where the cell runs it ---------------------------

#: pg_see2017's vocabulary (50 000 + 128 OOV buckets) and beam; every
#: other width is small: the selection is chosen from the row alone
PG_WIDE = HParams(batch_size=2, hidden_dim=8, emb_dim=6, vocab_size=50000,
                  max_oov_buckets=128, beam_size=4, max_enc_steps=12,
                  max_dec_steps=8, min_dec_steps=2, mode="decode",
                  decode_enc_block=4)
TF_WIDE = PG_WIDE.replace(model_family="transformer", emb_dim=8, num_heads=2,
                          enc_layers=1, dec_layers=1)
WIDE = PG_WIDE.vocab_size + PG_WIDE.max_oov_buckets  # 50 128


def test_the_detector_sees_a_stock_top_k():
    x = jnp.zeros((2, 4, WIDE), jnp.float32)
    stock = jax.jit(lambda r: jax.lax.top_k(r, 8)).lower(x).compile()
    assert wide_row_orderings(stock.as_text(), WIDE)
    ours = jax.jit(lambda r: topk.top_k(r, 8)).lower(x).compile()
    assert not wide_row_orderings(ours.as_text(), WIDE)


def _passes(k: int) -> int:
    """The reads of the cell's vocabulary-wide row that selecting k may
    make."""
    return -(-k // topk._plan(WIDE, k))


@pytest.mark.parametrize("m", [1, 2, 4, 8])
def test_the_detector_sees_the_parents_eight(m):
    """One pick a pass (the parent's form, ISSUE 26) is eight reduces
    over the row under the slot step's two `vmap`s; m a pass are 8 / m,
    and the row's top and mass are none of them."""
    x = jnp.zeros((2, 4, WIDE), jnp.float32)
    text = jax.jit(jax.vmap(jax.vmap(lambda r: (
        topk._select(r, 8, m=m), r.max(-1), r.sum(-1))))).lower(
            x).compile().as_text()
    assert len(wide_reduces(text, WIDE)) == 8 // m
    assert not wide_reduces(text, WIDE + 1)
    ours = jax.jit(jax.vmap(jax.vmap(lambda r: topk.top_k(r, 8)))).lower(
        x).compile().as_text()
    assert 1 <= len(wide_reduces(ours, WIDE)) <= _passes(8) < 8


def _builds_no_extended_row(text: str, hps: HParams) -> None:
    """The slot step ranks candidates (ops/topk.mixture_top_k): the
    vocabulary's own rows are 50 000 wide, none is sorted, none is
    scattered into, no instruction has the extended width, and the
    selection reads a row at most once for every ``_plan`` picks of the
    2 x beam (ISSUE 37; the parent read it once a pick)."""
    V = hps.vocab_size
    assert wide_dimensions(text, V)  # the step does hold the vocabulary
    assert 1 <= len(wide_reduces(text, V)) <= _passes(2 * hps.beam_size)
    assert not wide_row_orderings(text, V)
    assert not wide_row_orderings(text, V + hps.max_oov_buckets)
    assert not wide_scatters(text, V)
    assert not wide_dimensions(text, V + hps.max_oov_buckets)


def test_the_detectors_see_the_extended_row():
    """The row as the parent's step built it: two slots of four rows."""
    hps = PG_WIDE
    dense = jax.jit(jax.vmap(lambda vd, attn, p, ids: topk.top_k(
        topk.extended_mixture(vd, attn, p, ids, WIDE), 8))).lower(
            jnp.zeros((2, 4, hps.vocab_size)), jnp.zeros((2, 4, 12)),
            jnp.zeros((2, 4)), jnp.zeros((2, 12), jnp.int32)).compile()
    text = dense.as_text()
    assert wide_dimensions(text, WIDE)
    assert wide_scatters(text, hps.vocab_size)
    with pytest.raises(AssertionError):
        _builds_no_extended_row(text, hps)


SLOTS = 3  # slots x beam = 12 rows: no parameter matrix's other width
SCORE_ROWS = SLOTS * PG_WIDE.beam_size
assert SCORE_ROWS not in (PG_WIDE.hidden_dim, PG_WIDE.emb_dim)


def test_the_detector_sees_a_gather_from_the_scores():
    """The parent's form, ``mixture_top_k`` with no scores supplied: the
    article's words are looked up in the [slots, beam, V] block.  With
    them supplied nothing is looked up there, and the once-a-loop
    gather of the head's columns reads a parameter matrix."""
    hps = PG_WIDE
    V, K, T = hps.vocab_size, hps.beam_size, hps.max_enc_steps
    z, attn = jnp.zeros((SLOTS, K, V)), jnp.zeros((SLOTS, K, T))
    p, ids = jnp.zeros((SLOTS, K)), jnp.zeros((SLOTS, T), jnp.int32)
    art = jnp.zeros((SLOTS, K, T))
    gathered = jax.jit(jax.vmap(lambda *a: topk.mixture_top_k(
        *a, 8, WIDE))).lower(z, attn, p, ids).compile().as_text()
    assert score_gathers(gathered, V, SCORE_ROWS)
    supplied = jax.jit(jax.vmap(lambda z, a, p, i, s: topk.mixture_top_k(
        z, a, p, i, 8, WIDE, s))).lower(z, attn, p, ids, art).compile()
    assert not wide_gathers(supplied.as_text(), V)
    head = jax.jit(lambda w, v, i: topk.head_at(w, v, i, 8)).lower(
        jnp.zeros((hps.hidden_dim, V)), jnp.zeros((V,)), ids).compile()
    assert wide_gathers(head.as_text(), V)
    assert not score_gathers(head.as_text(), V, SCORE_ROWS)


def test_engine_slot_step_at_the_cells_vocabulary_orders_no_wide_row(tmp_path):
    """The engine's own executable (SlotDecodeEngine.compiled_step(),
    behind ServingServer.compiled_slot_step()): three slots over an
    8-page arena at pg_see2017's vocabulary and beam.  The article's
    words are scored by a product with the head's columns (ISSUE 33):
    what is still looked up by word id is a parameter matrix, the
    embedding in the loop and the projection once before it, never the
    step's scores."""
    from textsummarization_on_flink_tpu.data.vocab import Vocab
    from textsummarization_on_flink_tpu.obs import Registry
    from textsummarization_on_flink_tpu.serve.server import ServingServer
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib

    words = ["the", "cat", "sat", "dog", "ran", "."]
    vocab = Vocab(words=words + [f"w{i}" for i in range(50000 - 4 - len(
        words))])
    assert vocab.size() == 50000
    hps = PG_WIDE.replace(
        max_enc_steps=16, max_dec_steps=4, min_dec_steps=1,
        serve_buckets="16", serve_mode="continuous", serve_slots=SLOTS,
        serve_refill_chunk=2, serve_arena_pages=8)
    params = trainer_lib.init_train_state(hps, vocab.size(), seed=0).params
    server = ServingServer(hps, vocab, params=params,
                           decode_root=str(tmp_path / "d"),
                           registry=Registry())
    with server:
        server.submit("the cat sat .", uuid="a").result(timeout=600)
        text = server.compiled_slot_step().as_text()
    _builds_no_extended_row(text, hps)
    assert wide_gathers(text, hps.vocab_size)  # the parameters' rows
    assert not score_gathers(text, hps.vocab_size, SCORE_ROWS)


def test_transformer_slot_step_at_the_cells_vocabulary_orders_no_wide_row():
    hps = TF_WIDE
    family = get_family(hps.model_family)
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(0))
    B = hps.batch_size
    arrays = ge._decode_arrays(hps, np.random.RandomState(1), B)
    pages = 6
    state = beam_search.init_slots_jit(params, hps, arrays, pages)
    table = np.full((B, 3), pages, np.int32)
    text = beam_search.step_slots_jit.lower(
        params, hps, state, np.ones(B, bool), table, 2).compile().as_text()
    _builds_no_extended_row(text, hps)
