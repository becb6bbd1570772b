"""On-device beam search vs a Python mirror of the reference algorithm.

The mirror re-implements /root/reference beam_search.py's hypothesis
bookkeeping (list-of-Hypothesis, sort by avg log prob, STOP/min_dec_steps
triage, 2*beam expansion, step-0 single-hyp expansion) on the host, calling
the SAME jitted decode_onestep — so any disagreement isolates the
lax.while_loop translation, not the numerics.
"""

import dataclasses

import jax
import numpy as np
import pytest
from _slots import Slots

from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.vocab import START_ID, STOP_ID, UNK_ID
from textsummarization_on_flink_tpu.decode import beam_search
from textsummarization_on_flink_tpu.models import pointer_generator as pg


HPS = HParams(batch_size=2, hidden_dim=8, emb_dim=6, vocab_size=24,
              max_enc_steps=12, max_dec_steps=8, beam_size=3,
              min_dec_steps=2, max_oov_buckets=4, mode="decode")


def make_arrays(hps, seed=0, B=None):
    rng = np.random.RandomState(seed)
    B = B or hps.batch_size
    T = hps.max_enc_steps
    lens = rng.randint(T // 2, T + 1, size=(B,)).astype(np.int32)
    mask = (np.arange(T)[None, :] < lens[:, None]).astype(np.float32)
    enc = (rng.randint(4, hps.vocab_size, size=(B, T)) * mask).astype(np.int32)
    ext = enc.copy()
    oov_pos = (rng.rand(B, T) < 0.15) & (mask > 0)
    ext[oov_pos] = hps.vocab_size + rng.randint(
        0, hps.max_oov_buckets, size=int(oov_pos.sum()))
    return {
        "enc_batch": enc,
        "enc_lens": lens,
        "enc_padding_mask": mask,
        "enc_batch_extend_vocab": ext,
    }


@dataclasses.dataclass
class Hyp:
    tokens: list
    lp: np.float32
    state: tuple  # (c, h) rows
    coverage: np.ndarray

    @property
    def avg(self):
        return self.lp / len(self.tokens)


def python_reference_search(params, hps, arrays, article_idx):
    """beam_search.py:82-168 transliterated, same decode_onestep."""
    one = {k: v[article_idx:article_idx + 1] for k, v in arrays.items()}
    enc = pg.run_encoder(params, hps, one)
    K = hps.beam_size
    T_enc = hps.max_enc_steps
    enc_k = pg.EncoderOutput(
        enc_states=np.broadcast_to(np.asarray(enc.enc_states),
                                   (K,) + enc.enc_states.shape[1:]),
        enc_features=np.broadcast_to(np.asarray(enc.enc_features),
                                     (K,) + enc.enc_features.shape[1:]),
        dec_in_state=None)
    c0 = np.asarray(enc.dec_in_state[0])[0]
    h0 = np.asarray(enc.dec_in_state[1])[0]
    mask_k = np.broadcast_to(one["enc_padding_mask"], (K, T_enc))
    ext_k = np.broadcast_to(one["enc_batch_extend_vocab"], (K, T_enc))
    step_fn = jax.jit(pg.decode_onestep, static_argnames=("hps",))

    hyps = [Hyp([START_ID], np.float32(0.0), (c0, h0),
                np.zeros(T_enc, np.float32)) for _ in range(K)]
    results = []
    steps = 0
    while steps < hps.max_dec_steps and len(results) < K:
        latest = np.array([h.tokens[-1] for h in hyps], np.int32)
        latest = np.where(latest >= hps.vocab_size, UNK_ID, latest)
        state = (np.stack([h.state[0] for h in hyps]),
                 np.stack([h.state[1] for h in hyps]))
        cov = np.stack([h.coverage for h in hyps])
        out = step_fn(params, hps, enc_k, mask_k, ext_k, latest, state, cov)
        topk_ids = np.asarray(out.topk_ids)
        topk_lp = np.asarray(out.topk_log_probs, np.float32)
        new_c = np.asarray(out.state[0])
        new_h = np.asarray(out.state[1])
        new_cov = np.asarray(out.coverage)

        all_hyps = []
        num_orig = 1 if steps == 0 else len(hyps)
        for i in range(num_orig):
            for j in range(2 * K):
                all_hyps.append(Hyp(
                    hyps[i].tokens + [int(topk_ids[i, j])],
                    np.float32(hyps[i].lp + topk_lp[i, j]),
                    (new_c[i], new_h[i]), new_cov[i]))
        hyps = []
        for h in sorted(all_hyps, key=lambda h: h.avg, reverse=True):
            if h.tokens[-1] == STOP_ID:
                if steps >= hps.min_dec_steps:
                    results.append(h)
            else:
                hyps.append(h)
            if len(hyps) == K or len(results) == K:
                break
        steps += 1
    if not results:
        results = hyps
    best = sorted(results, key=lambda h: h.avg, reverse=True)[0]
    return best


@pytest.fixture(scope="module")
def params():
    return pg.init_params(HPS, HPS.vocab_size, jax.random.PRNGKey(42))


@pytest.mark.parametrize("beam_size", [1, None])  # 1 = greedy degenerate
@pytest.mark.parametrize("coverage", [False, True])
@pytest.mark.parametrize("seed", [0, 7])
def test_matches_python_reference(params, coverage, seed, beam_size):
    hps = HPS.replace(coverage=coverage)
    if beam_size is not None:
        hps = hps.replace(beam_size=beam_size)
    arrays = make_arrays(hps, seed=seed)
    out = beam_search.run_beam_search(params, hps, arrays)
    for b in range(hps.batch_size):
        ref = python_reference_search(params, hps, arrays, b)
        n = int(out.length[b])
        got = list(out.tokens[b][:n])
        assert got == ref.tokens, (b, got, ref.tokens)
        np.testing.assert_allclose(out.avg_log_prob[b], ref.avg,
                                   rtol=2e-5, atol=2e-6)


def test_output_invariants(params):
    arrays = make_arrays(HPS, seed=3)
    out = beam_search.run_beam_search(params, HPS, arrays)
    B = HPS.batch_size
    assert out.tokens.shape == (B, HPS.max_dec_steps + 1)
    assert out.attn_dists.shape == (B, HPS.max_dec_steps, HPS.max_enc_steps)
    assert out.p_gens.shape == (B, HPS.max_dec_steps)
    for b in range(B):
        n = int(out.length[b])
        toks = out.tokens[b][:n]
        assert toks[0] == START_ID
        assert 2 <= n <= HPS.max_dec_steps + 1
        # every id inside the static extended vocab
        assert toks.max() < HPS.vocab_size + HPS.max_oov_buckets
        if toks[-1] == STOP_ID:
            # STOP accepted only after min_dec_steps generations
            assert n - 2 >= HPS.min_dec_steps
        assert np.isfinite(out.avg_log_prob[b])
        # attention rows for generated steps are distributions over valid pos
        L = int(arrays["enc_lens"][b])
        for t in range(n - 1):
            row = out.attn_dists[b, t]
            np.testing.assert_allclose(row.sum(), 1.0, atol=1e-4)
            assert row[L:].sum() < 1e-6


@pytest.mark.parametrize("kind", ["scan", "chunked"])
@pytest.mark.parametrize("coverage", [False, True])
def test_loop_kinds_match_while_loop(params, coverage, kind):
    """TS_BEAM_LOOP=scan (fixed trip count, masked updates) and =chunked
    (while over scan chunks — early exit at chunk granularity, ceil(T/C)
    dynamic iterations on RPC-proxied backends) must be token-exact with
    the early-exit while_loop."""
    # chunk=3 does NOT divide max_dec_steps: the masked inner scan must
    # make the overshoot a no-op (chunk is a static jit cache-key arg)
    chunk = 3 if kind == "chunked" else None
    hps = HPS.replace(coverage=coverage)
    arrays = make_arrays(hps, seed=5)
    a = beam_search.run_beam_search_jit(params, hps, arrays, loop="while")
    b = beam_search.run_beam_search_jit(params, hps, arrays, loop=kind,
                                        chunk=chunk)
    np.testing.assert_array_equal(np.asarray(a.tokens), np.asarray(b.tokens))
    np.testing.assert_array_equal(np.asarray(a.length), np.asarray(b.length))
    np.testing.assert_allclose(np.asarray(a.avg_log_prob),
                               np.asarray(b.avg_log_prob), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(a.attn_dists),
                               np.asarray(b.attn_dists), atol=1e-6)


@pytest.mark.parametrize("chunk", [1, 3, 5, 13])
def test_chunked_early_exit_parity_any_chunk(params, chunk):
    """The chunked loop must stay token-exact with the early-exit while
    loop for EVERY tail-chunk shape (ISSUE 6 satellite): chunk=1
    (degenerate — every step a boundary), 3 and 5 (neither divides
    max_dec_steps=8, so the final chunk overshoots the horizon and the
    masked inner scan must no-op the tail), and 13 (> max_dec_steps —
    one chunk covers the whole search).  The slot loop steps the same
    masked chunk body, so this parity is what continuous serving's
    refill boundaries rest on."""
    arrays = make_arrays(HPS, seed=11)
    a = beam_search.run_beam_search_jit(params, HPS, arrays, loop="while")
    b = beam_search.run_beam_search_jit(params, HPS, arrays, loop="chunked",
                                        chunk=chunk)
    np.testing.assert_array_equal(np.asarray(a.tokens), np.asarray(b.tokens))
    np.testing.assert_array_equal(np.asarray(a.length), np.asarray(b.length))
    np.testing.assert_allclose(np.asarray(a.avg_log_prob),
                               np.asarray(b.avg_log_prob), rtol=1e-6)


def test_chunked_parity_when_no_beam_finishes(params):
    """Tail-chunk parity in the no-early-exit regime: min_dec_steps
    near the horizon forces every article through max_dec_steps, so the
    final (partial) chunk runs right up against the masked boundary."""
    hps = HPS.replace(min_dec_steps=HPS.max_dec_steps - 1)
    arrays = make_arrays(hps, seed=4)
    a = beam_search.run_beam_search_jit(params, hps, arrays, loop="while")
    b = beam_search.run_beam_search_jit(params, hps, arrays, loop="chunked",
                                        chunk=3)
    np.testing.assert_array_equal(np.asarray(a.tokens), np.asarray(b.tokens))
    np.testing.assert_array_equal(np.asarray(a.length), np.asarray(b.length))


class TestSlotSearch:
    """The continuous-batching slot kernels (pack/step/unpack over a
    persistent state, driven through tests/_slots.py over the default
    arena) against the batch search: identical per-article
    trajectories, per-slot activity masking, and a jit cache that never
    grows with slot index or occupancy."""

    def test_slot_parity_with_batch_search(self, params):
        """Articles packed into arbitrary slots, stepped with a chunk
        that does NOT divide max_dec_steps, finish token-exact with the
        one-dispatch batch search."""
        arrays = make_arrays(HPS, seed=0)
        ref = beam_search.run_beam_search(params, HPS, arrays)
        eng = Slots(params, HPS, arrays, slots=3)
        placement = {2: 0, 0: 1}  # slot -> article
        for slot, art in placement.items():
            eng.pack(slot, {k: v[art:art + 1] for k, v in arrays.items()})
        done, _ = eng.drive([True, False, True], chunk=3)
        assert sorted(done) == sorted(placement)
        for slot, art in placement.items():
            out = done[slot]
            n = int(out.length)
            n_ref = int(ref.length[art])
            assert n == n_ref
            assert list(np.asarray(out.tokens)[:n]) == \
                list(ref.tokens[art][:n_ref])
            np.testing.assert_allclose(np.asarray(out.avg_log_prob),
                                       ref.avg_log_prob[art], rtol=1e-6)
            np.testing.assert_allclose(np.asarray(out.attn_dists),
                                       ref.attn_dists[art], atol=1e-6)

    def test_inactive_slots_never_finish_and_refill_is_exact(self, params):
        """An inactive slot's garbage state never reports finished, and
        packing a NEW article into a just-retired slot reproduces that
        article's batch-search result exactly — the refill contract the
        continuous scheduler depends on."""
        arrays = make_arrays(HPS, seed=9)
        ref = beam_search.run_beam_search(params, HPS, arrays)
        eng = Slots(params, HPS, arrays, slots=2)
        eng.pack(1, {k: v[0:1] for k, v in arrays.items()})
        fin = eng.step([False, True], 2)
        assert not bool(fin[0])  # inactive slot stays silent
        # retire slot 1 whenever it finishes, then REFILL it with
        # article 1 and check the second tenancy end to end
        done, _ = eng.drive([False, True], chunk=2)
        assert 1 in done
        eng.pack(1, {k: v[1:2] for k, v in arrays.items()})
        done2, _ = eng.drive([False, True], chunk=2)
        out = done2[1]
        n = int(out.length)
        assert list(np.asarray(out.tokens)[:n]) == \
            list(ref.tokens[1][:int(ref.length[1])])

    def test_slot_kernels_compile_once(self, params):
        """Slot index, occupancy pattern, and article content are all
        traced — after the first pack/step/unpack, serving more articles
        through different slots adds ZERO jit-cache entries (the
        'no per-request recompiles' acceptance claim at kernel level)."""
        arrays = make_arrays(HPS, seed=2)
        eng = Slots(params, HPS, arrays, slots=3)
        eng.pack(0, {k: v[0:1] for k, v in arrays.items()})
        eng.step([True, False, False], 3)
        eng.unpack(0)
        sizes = {f: f._cache_size()
                 for f in (beam_search.pack_slot_jit,
                           beam_search.step_slots_jit,
                           beam_search.unpack_slot_jit)}
        for slot, art in ((1, 1), (2, 0), (0, 1)):
            eng.pack(slot, {k: v[art:art + 1] for k, v in arrays.items()})
        eng.step([True, True, True], 3)
        eng.unpack(2)
        for f, before in sizes.items():
            assert f._cache_size() == before, f


def test_min_dec_steps_blocks_early_stop(params):
    # with min_dec_steps == max-1, any STOP before the horizon is discarded,
    # so results are either long or the live-beam fallback
    hps = HPS.replace(min_dec_steps=HPS.max_dec_steps - 1)
    arrays = make_arrays(hps, seed=1)
    out = beam_search.run_beam_search(params, hps, arrays)
    for b in range(hps.batch_size):
        assert int(out.length[b]) >= hps.max_dec_steps
