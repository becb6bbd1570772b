"""ops.topk.mixture_top_k against top_k(extended_mixture(softmax(...))):
the contract the beam step's candidate ranking stands on (ISSUE 31) —
ids equal, values within 2e-6 relative — and token-equal decodes of the
three families through both slot engines with the candidates in place
of the extended row.  Since ISSUE 33 a caller that decodes in a loop
supplies the article-side scores (a product with ``head_at``'s
columns) in place of the gather from the row: the same contract, up to
the float32 accumulation order of an article word's logit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _slots import Slots

import __graft_entry__ as ge
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.models import get_family
from textsummarization_on_flink_tpu.ops import topk
from textsummarization_on_flink_tpu.ops.losses import project_scores

V, T, OOV = 1024, 24, 8  # the candidates' side of _mixture_plan
LEAD = (3, 2)  # slots, beam: the two axes the slot step vmaps over


def _scores(rng, v, lead=LEAD):
    return rng.normal(size=lead + (v,)).astype(np.float32) * 3


def _base(rng, v=V, t=T):
    """Vocabulary scores, an attention row, p_gen and distinct
    in-vocabulary article ids for every row of LEAD."""
    attn = rng.random(LEAD + (t,)).astype(np.float32)
    attn /= attn.sum(-1, keepdims=True)
    ids = np.stack([rng.choice(v, size=t, replace=False)
                    for _ in range(int(np.prod(LEAD)))]).reshape(
                        LEAD + (t,)).astype(np.int32)
    p = rng.uniform(0.2, 0.8, size=LEAD).astype(np.float32)
    return _scores(rng, v), attn, p, ids


def _best(z, n):
    return np.argsort(-z, axis=-1, kind="stable")[..., :n]


def _duplicate_ids(rng):
    z, attn, p, ids = _base(rng)
    ids[..., 1::2] = ids[..., ::2]  # every id twice
    ids[..., :6] = ids[..., :1]  # and one six times
    return z, attn, p, ids


def _oov_ids(rng):
    """In-article OOV ids at and past V, one of them twice, one in the
    last bucket, with enough attention to be picked."""
    z, attn, p, ids = _base(rng)
    ids[..., 3], ids[..., 7], ids[..., 11] = V, V + 2, V + 2
    ids[..., 5] = V + OOV - 1
    attn[..., [3, 5, 7]] += 0.5
    return z, attn / attn.sum(-1, keepdims=True), p, ids


def _past_the_row(rng):
    """Ids past the extended row, attended: the scatter drops them, and
    so do the candidates; a bucket id beside them stays."""
    z, attn, p, ids = _base(rng)
    ids[..., 2], ids[..., 9], ids[..., 10] = V + OOV, V + OOV + 5, V + 1
    attn[..., [2, 9, 10]] += 0.5
    return z, attn / attn.sum(-1, keepdims=True), p, ids


def _padded_tail(rng):
    """Padding: id 0 with zero attention after a short article."""
    z, attn, p, ids = _base(rng)
    attn[..., T // 2:] = 0.0
    ids[..., T // 2:] = 0
    return z, attn / attn.sum(-1, keepdims=True), p, ids


def _article_holds_the_vocabularys_best(rng):
    z, attn, p, ids = _base(rng)
    ids[..., :5] = _best(z, 5)  # the whole of a k = 2 pick, most of k = 8
    return z, attn, p, ids


def _article_outside_the_vocabularys_best(rng):
    """No article id among the vocabulary's best; copy mass lifts a few
    of the vocabulary's worst over those."""
    z, attn, p, ids = _base(rng)
    ids[...] = np.argsort(z, axis=-1, kind="stable")[..., :T]
    attn[..., :3] += 1.0
    return z, attn / attn.sum(-1, keepdims=True), p, ids


def _ties_across_the_sets(rng):
    """Exact ties between a vocabulary pick and an article id, on both
    sides of it by id: equal scores are equal probabilities, whichever
    way a probability is made (the row's division, or the gathered
    score's), and an article id with no attention on it is worth the
    vocabulary's share alone."""
    del rng
    z = np.full(LEAD + (V,), -4.0, np.float32)
    z[..., [10, 500, 900]] = 2.0  # three at the top
    z[..., [20, 600]] = 1.0
    z[..., 40:60] = 0.0  # more ties than places
    ids = np.tile(np.arange(100, 100 + T, dtype=np.int32), LEAD + (1,))
    z[..., 100:100 + T] = -9.0  # the filler ids: out of the running
    ids[..., 0], ids[..., 1] = 5, 700  # unattended: tie the top from
    z[..., [5, 700]] = 2.0  # below and above by id, across the sets
    ids[..., 2], ids[..., 3] = 600, 600  # held by both sets, attended twice
    ids[..., 4], ids[..., 5] = V + 1, V + 3  # two OOV ids tying each other
    ids[..., 6], ids[..., 7] = 45, 52  # inside the run of ties, unattended
    attn = np.zeros(LEAD + (T,), np.float32)
    attn[..., [2, 3]] = 2 / 64
    attn[..., [4, 5]] = 4 / 64
    attn[..., 8:] = 1 / 64
    p = np.full(LEAD, 0.5, np.float32)
    return z, attn, p, ids


def _p_gen_0(rng):
    """All copy: outside the article every word ties at 0 and goes by id."""
    z, attn, p, ids = _duplicate_ids(rng)
    return z, attn, np.zeros_like(p), ids


def _p_gen_1(rng):
    """No copy: the article's ids rank by the vocabulary alone."""
    z, attn, p, ids = _article_holds_the_vocabularys_best(rng)
    return z, attn, np.ones_like(p), ids


def _one_rounding(make):
    """``make``'s case with p_gen 0, 0.5 or 1 and at most ONE attended
    position an id, its attention a small multiple of 2**-6: the
    extended row then takes one rounded addition a word on either path,
    so bfloat16 may be compared to the bit as well (a repeated id's sum
    is rounded in another order by the scatter-add, there as here)."""
    def made(rng):
        z, attn, p, ids = make(rng)
        attn = np.minimum(np.round(attn / attn.max() * 8) / 64, 1 / 8)
        flat_i, flat_a = ids.reshape(-1, ids.shape[-1]), attn.reshape(
            -1, attn.shape[-1])
        for row_i, row_a in zip(flat_i, flat_a):
            seen = set()
            for t, w in enumerate(row_i):
                if int(w) in seen:
                    row_a[t] = 0.0
                seen.add(int(w))
        p = np.where((p > 0) & (p < 1), 0.5, p).astype(np.float32)
        return z, attn.astype(np.float32), p, ids
    return made


CASES = {"duplicate_ids": _duplicate_ids, "oov_ids": _oov_ids,
         "past_the_row": _past_the_row, "padded_tail": _padded_tail,
         "article_holds_best": _article_holds_the_vocabularys_best,
         "article_outside_best": _article_outside_the_vocabularys_best,
         "ties_across_sets": _ties_across_the_sets,
         "p_gen_0": _p_gen_0, "p_gen_1": _p_gen_1}


def _dense_fallback(rng):
    """Under MIN_ROW the extended row is built, as before."""
    z, attn, p, ids = _base(rng, v=64, t=12)
    ids[..., :3], ids[..., 3] = _best(z, 3), 64 + 1
    return z, attn, p, ids


def _cell_width(rng):
    """pg_see2017's row: 50 000 + 128, 400 article positions."""
    v, t = 50000, 400
    attn = rng.random(LEAD + (t,)).astype(np.float32)
    attn /= attn.sum(-1, keepdims=True)
    ids = np.minimum(rng.pareto(0.6, size=LEAD + (t,)) * 4, v - 1)
    ids = ids.astype(np.int32)
    ids[..., ::50] = v + rng.integers(0, 128, size=LEAD + (t // 50,))
    return (_scores(rng, v), attn,
            rng.uniform(0.2, 0.8, size=LEAD).astype(np.float32), ids)


def _projected(rng, compute_dtype, hidden=32):
    """A row that IS a projection, ``h @ W + b`` through the program's
    own matmul, with the head it came from: an article that holds three
    of each row's best words (one of them twice), bucket ids, an id
    past the extended row and repeats.  Returns the case and (h, W, b)
    for the caller's side of the product."""
    _, attn, p, ids = _duplicate_ids(rng)
    h = rng.normal(size=LEAD + (hidden,)).astype(np.float32)
    w = rng.normal(size=(hidden, V)).astype(np.float32) * 0.5
    b = rng.normal(size=(V,)).astype(np.float32)
    z = np.asarray(project_scores(jnp.asarray(h), jnp.asarray(w),
                                  compute_dtype) + b)
    # shared by the beam's rows under vmap, so each slot's hypothesis 0's
    ids[..., 6:9] = _best(z, 3)[:, :1]
    ids[..., 9] = ids[..., 6]
    ids[..., 3], ids[..., 5], ids[..., 7] = V + 2, V + OOV - 1, V + OOV + 3
    attn[..., [3, 5, 6, 7]] += 0.3
    return (z, attn / attn.sum(-1, keepdims=True), p, ids), (h, w, b)


#: where the article-side scores come from: gathered from the row by
#: mixture_top_k itself (a caller with no loop); supplied, as the exact
#: product of a one-hot state with the case's own rows (every crafted
#: tie survives); or projected, the row and the article's scores both
#: real products of one random head (float32 and bfloat16 operands)
ART = ("gathered", "supplied", "projected")


def _params():
    out = [pytest.param(name, V + OOV, k, dtype, vmapped, art,
                        id=f"{name}-k{k}-{dtype}-{how}"
                        + ("" if art == "gathered" else "-" + art))
           for art in ART[:2] for name in CASES for k in (2, 8)
           for dtype in ("float32", "bfloat16")
           for vmapped, how in ((False, "plain"), (True, "vmap"))]
    out += [pytest.param("dense_fallback", 64 + 4, k, "float32", vm,
                         "gathered", id=f"dense_fallback-k{k}-{how}")
            for k in (2, 8) for vm, how in ((False, "plain"), (True, "vmap"))]
    out += [pytest.param("cell_width", 50128, 8, "float32", True, art,
                         id="cell_width-k8-float32-vmap"
                         + ("" if art == "gathered" else "-" + art))
            for art in ART[:2]]
    out += [pytest.param("projected", V + OOV, k, dtype, vmapped,
                         "projected", id=f"projected-k{k}-{dtype}-{how}")
            for k in (2, 8) for dtype in ("float32", "bfloat16")
            for vmapped, how in ((False, "plain"), (True, "vmap"))]
    return out


def _head_scores(h, w, b, ids, k, compute_dtype, vmapped):
    """A caller's side: the head's columns at the ids (``head_at``,
    once) and the product a step makes with them, through the matmul
    the row came from."""
    head = topk.head_at(w, b, ids, k)
    assert head is not None and head.w.shape == ids.shape + h.shape[-1:]

    def one(x, hw, hv):  # [rows, H] on [T, H], as pg.head_scores
        return project_scores(x, hw.T, compute_dtype) + hv

    if vmapped:  # ids [S, T] under the beam's rows [S, K, H]
        return jax.vmap(one)(h, head.w, head.v)
    return jax.vmap(one)(h[:, None], head.w, head.v)[:, 0]  # ids [R, T]


@pytest.mark.parametrize("case,ext_size,k,dtype,vmapped,art", _params())
def test_mixture_top_k_is_top_k_of_the_mixture(case, ext_size, k, dtype,
                                               vmapped, art):
    rng = np.random.default_rng(31)
    make = {"dense_fallback": _dense_fallback,
            "cell_width": _cell_width}.get(case) or CASES.get(case)
    if case == "projected":  # float32 arrays; dtype is the matmul's
        (z, attn, p, ids), (h, w, b) = _projected(rng, dtype)
        compute_dtype, dtype = dtype, "float32"
    else:
        if dtype == "bfloat16":
            make = _one_rounding(make)
        z, attn, p, ids = make(rng)
    z, attn, p = (jnp.asarray(x).astype(dtype) for x in (z, attn, p))
    plan = topk._mixture_plan(z.shape[-1], attn.shape[-1], k)
    assert plan == ("dense" if case == "dense_fallback" else "candidates")
    if art == "supplied":  # row r is state e_r on the rows themselves
        rows = int(np.prod(LEAD))
        h = jnp.eye(rows, dtype=z.dtype).reshape(LEAD + (rows,))
        w, b = z.reshape(rows, -1), jnp.zeros(z.shape[-1:], z.dtype)
        compute_dtype = "float32"  # exact in either: ones and zeros

    def want(z, attn, p, ids):
        return jax.lax.top_k(topk.extended_mixture(
            jax.nn.softmax(z, axis=-1), attn, p, ids, ext_size), k)

    def got(z, attn, p, ids, *scores):
        return topk.mixture_top_k(z, attn, p, ids, k, ext_size, *scores)

    if vmapped:  # over slots, the beam's rows sharing the article's ids
        ids = jnp.asarray(ids[:, 0])
        want, got = jax.vmap(want), jax.vmap(got)
    else:  # rank 2, every row its own ids
        z, attn, p, ids = (jnp.reshape(x, (-1,) + x.shape[len(LEAD):])
                           for x in (z, attn, p, jnp.asarray(ids)))
    scores = ()
    if art != "gathered":
        if not vmapped:
            h = h.reshape((-1,) + h.shape[len(LEAD):])
        scores = (_head_scores(h, jnp.asarray(w), jnp.asarray(b), ids, k,
                               compute_dtype, vmapped).astype(dtype),)
        if case in ("oov_ids", "past_the_row", "projected"):
            # some id is past the vocabulary: its column is place 0's
            assert (np.asarray(ids) >= z.shape[-1]).any()
    want_v, want_i = jax.jit(want)(z, attn, p, ids)
    got_v, got_i = jax.jit(got)(z, attn, p, ids, *scores)
    assert got_v.dtype == want_v.dtype and got_i.dtype == want_i.dtype
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_allclose(
        np.asarray(got_v.astype(jnp.float32)),
        np.asarray(want_v.astype(jnp.float32)),
        rtol=0 if dtype == "bfloat16" else 2e-6, atol=0)


def test_mixture_plan_is_pinned():
    """The one choice, from the two shapes a trace can see: candidates
    at the cell's shape and at the Reach queue's vocabulary, the dense
    row at a test vocabulary's, at a k the selection does not take, and
    where the article is long beside the row."""
    assert topk._mixture_plan(50000, 400, 8) == "candidates"
    assert topk._mixture_plan(50000, 400, 2) == "candidates"
    assert topk._mixture_plan(151936, 4096, 8) == "candidates"
    assert topk._mixture_plan(24, 12, 8) == "dense"
    assert topk._mixture_plan(400, 12, 8) == "dense"
    assert topk._mixture_plan(50000, 400, 64) == "dense"
    assert topk._mixture_plan(2048, 400, 8) == "dense"


# -- token-equal decodes, candidates against the extended row ---------------

def _decode_hps(family: str) -> HParams:
    """The tests' small configuration with a vocabulary just long enough
    for the candidates ((8 + 12) * 8 <= 640)."""
    hps = HParams(batch_size=3, hidden_dim=8, emb_dim=8, vocab_size=640,
                  max_oov_buckets=4, beam_size=4, max_enc_steps=12,
                  max_dec_steps=7, min_dec_steps=2, mode="decode",
                  decode_enc_block=4, model_family=family)
    if family != "pointer_generator":
        hps = hps.replace(num_heads=2, enc_layers=1, dec_layers=1)
    return hps


def _decode(hps, params, arrays, chunk: int = 3):
    """Every article of `arrays` through the slot kernels, one slot
    each: [(tokens, avg_log_prob)]."""
    B = arrays["enc_lens"].shape[0]
    eng = Slots(params, hps, arrays, B)
    for i in range(B):
        eng.pack(i, {k: v[i:i + 1] for k, v in arrays.items()})
    done, _ = eng.drive(chunk=chunk, max_chunks=hps.max_dec_steps)
    assert sorted(done) == list(range(B))
    return [(list(np.asarray(done[i].tokens)[:int(done[i].length)]),
             float(done[i].avg_log_prob)) for i in range(B)]


@pytest.mark.parametrize("family", ["pointer_generator", "transformer",
                                    "avg_attention"])
def test_decodes_are_token_equal_with_the_candidates(family, monkeypatch):
    hps = _decode_hps(family)
    params = get_family(family).init_params(hps, hps.vocab_size,
                                            jax.random.PRNGKey(3))
    arrays = ge._decode_arrays(hps, np.random.RandomState(5), hps.batch_size)
    # the article's words again and again, and OOV ids: copies that win
    ext = arrays["enc_batch_extend_vocab"]
    ext[:, 1::2] = ext[:, ::2]
    ext[:, 4] = hps.vocab_size + 1
    calls = []
    real = topk._mixture_candidates
    monkeypatch.setattr(topk, "_mixture_candidates",
                        lambda *a: calls.append(1) or real(*a))
    got = _decode(hps, params, arrays)
    assert calls  # the candidates ranked, not the row
    del calls[:]
    monkeypatch.setattr(topk, "_mixture_plan", lambda *a: "dense")
    # another static argument: nothing traced above is found again
    want = _decode(hps.replace(exp_name="the-extended-row"), params, arrays)
    assert not calls
    for (g_tok, g_lp), (w_tok, w_lp) in zip(got, want):
        assert g_tok == w_tok
        assert g_lp == pytest.approx(w_lp, rel=1e-5)
