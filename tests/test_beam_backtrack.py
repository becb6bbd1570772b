"""Backtrack-reconstruction parity vs a materialized-history mirror
(ISSUE 7).

The decode byte diet replaced the beam search's per-hypothesis
trajectory buffers (tokens/attention/p_gen gathered by parent every
step) with backpointer columns and a `_finalize_beam` backtrack.  This
module re-implements the PRE-PR bookkeeping — full per-hypothesis
buffers, host-side, gathered by parent each step — around the SAME
jitted family step closures, so any disagreement isolates the
backpointer/backtrack translation, not the numerics.  Pinned for BOTH
model families across all three loop kinds and the slot kernels, plus
the bf16 KV-cache drift envelope and the engine compile-count claim.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _slots import Slots
from test_beam_search import make_arrays

from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.config import HParams, resolve_arena_pages
from textsummarization_on_flink_tpu.data.vocab import START_ID, STOP_ID, UNK_ID
from textsummarization_on_flink_tpu.decode import beam_search
from textsummarization_on_flink_tpu.models import get_family
from textsummarization_on_flink_tpu.obs import Registry
from textsummarization_on_flink_tpu.obs import profile as profile_lib

PG_HPS = HParams(batch_size=2, hidden_dim=8, emb_dim=6, vocab_size=24,
                 max_enc_steps=12, max_dec_steps=8, beam_size=3,
                 min_dec_steps=2, max_oov_buckets=4, mode="decode")
TF_HPS = PG_HPS.replace(model_family="transformer", hidden_dim=8, emb_dim=8,
                        num_heads=2, enc_layers=2, dec_layers=2)
# the AAN draft family (ISSUE 10) rides the same generic mirror: its
# beam-adapter parity through while/scan/chunked AND the slot kernels
# is exactly this module's parametrization
AAN_HPS = TF_HPS.replace(model_family="avg_attention")

FAMILY_CASES = [
    pytest.param("pointer_generator", PG_HPS, id="pg"),
    pytest.param("transformer", TF_HPS, id="tf"),
    pytest.param("avg_attention", AAN_HPS, id="aan"),
]


@dataclasses.dataclass
class Hyp:
    """One materialized hypothesis: FULL token/attention/p_gen
    trajectories carried explicitly — the pre-PR representation."""

    tokens: list
    lp: np.float32
    attn: list  # one [T_enc] row per generated token
    pgens: list
    slot: int  # row in the stacked device state

    @property
    def avg(self):
        return self.lp / len(self.tokens)


def materialized_search(params, hps, family, arrays, b):
    """The pre-PR search transliterated to the host: list-of-Hypothesis
    with materialized histories, parent gathers via tree_map(x[parents])
    on the family's opaque decode state, same triage order."""
    enc_view = family.beam_encode(params, hps, arrays)
    enc_one = jax.tree_util.tree_map(lambda x: x[b], enc_view)
    mask = jnp.asarray(arrays["enc_padding_mask"][b])
    ext = jnp.asarray(arrays["enc_batch_extend_vocab"][b])
    init_state_fn, step_fn = family.beam_adapter(hps)
    state = init_state_fn(params, enc_one)
    step_jit = jax.jit(lambda t, latest, st: step_fn(
        params, enc_one, mask, ext, t, latest, st))
    K = hps.beam_size
    hyps = [Hyp([START_ID], np.float32(0.0), [], [], i) for i in range(K)]
    results = []
    steps = 0
    while steps < hps.max_dec_steps and len(results) < K:
        latest = np.array([h.tokens[-1] for h in hyps], np.int32)
        latest = np.where(latest >= hps.vocab_size, UNK_ID, latest)
        out = step_jit(jnp.int32(steps), jnp.asarray(latest), state)
        topk_ids = np.asarray(out.topk_ids)
        topk_lp = np.asarray(out.topk_log_probs, np.float32)
        attn = np.asarray(out.attn_dist)
        pgen = np.asarray(out.p_gen)
        cands = []  # hyp-major, like the device's stable argsort
        num_orig = 1 if steps == 0 else K
        for i in range(num_orig):
            for j in range(2 * K):
                cands.append((hyps[i], int(topk_ids[i, j]),
                              np.float32(hyps[i].lp + topk_lp[i, j]), i))
        new_hyps = []
        for h, tok, lp, parent in sorted(cands, key=lambda c: -c[2]):
            if tok == STOP_ID:
                if steps >= hps.min_dec_steps:
                    results.append(Hyp(h.tokens + [tok], lp,
                                       h.attn + [attn[parent]],
                                       h.pgens + [pgen[parent]], -1))
            else:
                new_hyps.append(Hyp(h.tokens + [tok], lp,
                                    h.attn + [attn[parent]],
                                    h.pgens + [pgen[parent]], parent))
            if len(new_hyps) == K or len(results) == K:
                break
        if len(results) < K:
            assert len(new_hyps) == K, "mirror beam underfilled"
        parents = np.array(
            [h.slot for h in new_hyps] + [0] * (K - len(new_hyps)),
            np.int32)
        state = jax.tree_util.tree_map(
            lambda x: x[jnp.asarray(parents)], out.state)
        for i, h in enumerate(new_hyps):
            h.slot = i
        hyps = new_hyps if new_hyps else hyps
        steps += 1
    pool = results if results else hyps
    return sorted(pool, key=lambda h: h.avg, reverse=True)[0]


def assert_matches_mirror(out, b, ref):
    """Device BeamSearchOutput row b vs a mirror Hyp: tokens exact,
    reconstructed attention/p_gen rows exact, zero-fill past the end."""
    n = int(out.length[b])
    assert list(np.asarray(out.tokens[b])[:n]) == ref.tokens
    np.testing.assert_allclose(np.asarray(out.avg_log_prob[b]), ref.avg,
                               rtol=2e-5, atol=2e-6)
    gen = n - 1  # generated tokens incl a final STOP, if any
    assert len(ref.attn) == gen
    np.testing.assert_allclose(np.asarray(out.attn_dists[b])[:gen],
                               np.stack(ref.attn), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out.p_gens[b])[:gen],
                               np.array(ref.pgens), rtol=1e-5, atol=1e-6)
    # rows past the trajectory are zero, exactly like the pre-PR buffers
    np.testing.assert_array_equal(np.asarray(out.attn_dists[b])[gen:], 0.0)
    np.testing.assert_array_equal(np.asarray(out.p_gens[b])[gen:], 0.0)


@pytest.mark.parametrize("loop", ["while", "scan", "chunked"])
@pytest.mark.parametrize("family_name,hps", FAMILY_CASES)
def test_backtrack_matches_materialized_mirror(family_name, hps, loop):
    """The tentpole parity claim: backpointer histories + the finalize
    backtrack reproduce the materialized-history search token-exactly
    (tokens, length, avg_log_prob, attn_dists, p_gens) for both model
    families and every loop kind."""
    family = get_family(family_name)
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(3))
    arrays = make_arrays(hps, seed=6)
    out = beam_search.run_beam_search_jit(
        params, hps, arrays, loop=loop,
        chunk=3 if loop == "chunked" else None)
    for b in range(hps.batch_size):
        ref = materialized_search(params, hps, family, arrays, b)
        assert_matches_mirror(out, b, ref)


@pytest.mark.parametrize("family_name,hps", FAMILY_CASES)
def test_backtrack_matches_mirror_no_early_exit(family_name, hps):
    """The live-beam fallback path of the backtrack (n_res == 0 at the
    horizon): min_dec_steps near the horizon discards most STOPs, so
    reconstruction anchors on the live beam."""
    hps = hps.replace(min_dec_steps=hps.max_dec_steps - 1)
    family = get_family(family_name)
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(5))
    arrays = make_arrays(hps, seed=2)
    out = beam_search.run_beam_search_jit(params, hps, arrays, loop="scan")
    for b in range(hps.batch_size):
        ref = materialized_search(params, hps, family, arrays, b)
        assert_matches_mirror(out, b, ref)


def _assert_slot_matches_mirror(out, ref):
    n = int(out.length)
    assert list(np.asarray(out.tokens)[:n]) == ref.tokens
    np.testing.assert_allclose(np.asarray(out.avg_log_prob), ref.avg,
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(out.attn_dists)[:n - 1],
                               np.stack(ref.attn), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("family_name,hps", FAMILY_CASES)
def test_slot_kernels_match_materialized_mirror(family_name, hps):
    """The slot kernels (continuous serving) run the same backpointer
    body per resident article: prefill -> pack -> chunked steps ->
    unpack must match the materialized mirror exactly, for both
    families (and the AAN draft tier)."""
    family = get_family(family_name)
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(3))
    arrays = make_arrays(hps, seed=6)
    slots = hps.batch_size
    eng = Slots(params, hps, arrays, slots)
    for slot in range(slots):
        eng.pack(slot, {k: v[slot:slot + 1] for k, v in arrays.items()})
    done, _ = eng.drive()
    assert sorted(done) == list(range(slots))
    for b in range(slots):
        ref = materialized_search(params, hps, family, arrays, b)
        _assert_slot_matches_mirror(done[b], ref)


# -- prefill/decode disaggregation parity (ISSUE 11) -----------------------
#
# The mirror is the FULL-WIDTH dense search; the slot path now prefills
# each article at its BUCKET shape and decodes with the valid-length
# mask and the blocked (conditional-chain) cross-attention.  Exactness
# across bucket lengths is the claim that disaggregation changed the
# COST story, not the numerics: the encoders are pad-invariant, the
# padded encoder tail sits behind the valid-length mask, and an
# uncovered key block's energies land on the same masked floor dense
# padding does.

#: articles engineered at the satellite's edge cases, as true lengths
#: against buckets (4, 8, 12) at the 12-wide test scale: a 1-token
#: article, one exactly AT a bucket boundary, one mid-bucket, and one
#: at the top bucket — packed together (mixed-length occupancy).
_DISAGG_LENS = (1, 4, 7, 12)
_DISAGG_BUCKETS = (4, 8, 12)


def _arrays_with_lens(hps, lens, seed=0):
    arrays = make_arrays(hps, seed=seed, B=len(lens))
    T = hps.max_enc_steps
    lens = np.asarray(lens, np.int32)
    mask = (np.arange(T)[None, :] < lens[:, None]).astype(np.float32)
    arrays["enc_lens"] = lens
    arrays["enc_padding_mask"] = mask
    arrays["enc_batch"] = (arrays["enc_batch"] * mask).astype(np.int32)
    ext = arrays["enc_batch_extend_vocab"]
    arrays["enc_batch_extend_vocab"] = np.where(mask > 0, ext,
                                                0).astype(np.int32)
    return arrays


def _one_at(arrays, row, bucket):
    """Article `row` as [1, bucket] arrays (what prefill_jit takes)."""
    return {k: (v[row:row + 1, :bucket] if v.ndim == 2
                else v[row:row + 1])
            for k, v in arrays.items()}


@pytest.mark.parametrize("family_name,hps", FAMILY_CASES)
def test_bucketed_prefill_matches_mirror_at_every_length(family_name, hps):
    """Mixed-length slot occupancy through the DISAGGREGATED path:
    each article prefilled at its own bucket (1-token -> bucket 4,
    boundary article -> its exact bucket, top-length article -> the
    resident width), decoded together under the blocked cross-attention
    in the multi-block regime (decode_enc_block=4 at T_enc=12), and
    every trajectory must still match the full-width materialized
    mirror token-exactly."""
    hps = hps.replace(batch_size=len(_DISAGG_LENS), decode_enc_block=4)
    family = get_family(family_name)
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(3))
    arrays = _arrays_with_lens(hps, _DISAGG_LENS, seed=6)
    slots = len(_DISAGG_LENS)
    eng = Slots(params, hps, arrays, slots)
    for slot, true_len in enumerate(_DISAGG_LENS):
        bucket = next(b for b in _DISAGG_BUCKETS if true_len <= b)
        pre = eng.prefill(_one_at(arrays, slot, bucket))
        assert int(np.asarray(pre.enc_valid_len)[0]) == true_len
        eng.pack(slot, pre)
    # the resident state records every article's TRUE length, not its
    # bucket or the padded width
    np.testing.assert_array_equal(
        np.asarray(eng.state.enc_valid_len), np.asarray(_DISAGG_LENS))
    done, _ = eng.drive()
    assert sorted(done) == list(range(slots))
    for b in range(slots):
        ref = materialized_search(params, hps, family, arrays, b)
        _assert_slot_matches_mirror(done[b], ref)


class TestBf16KVCache:
    """--decode_cache_dtype=bfloat16 (transformer): the cache narrows in
    storage only — attention math stays f32 — with a pinned drift
    envelope vs the f32 cache."""

    def _outputs(self, dtype):
        hps = TF_HPS.replace(decode_cache_dtype=dtype)
        family = get_family("transformer")
        params = family.init_params(hps, hps.vocab_size,
                                    jax.random.PRNGKey(7))
        arrays = make_arrays(hps, seed=4)
        return beam_search.run_beam_search_jit(params, hps, arrays,
                                               loop="scan")

    def test_pg_family_ignores_cache_dtype(self):
        """The LSTM family has no KV cache: bf16 must be a no-op."""
        hps = PG_HPS.replace(decode_cache_dtype="bfloat16")
        family = get_family("pointer_generator")
        params = family.init_params(hps, hps.vocab_size,
                                    jax.random.PRNGKey(7))
        arrays = make_arrays(hps, seed=4)
        a = beam_search.run_beam_search_jit(params, hps, arrays, loop="scan")
        b = beam_search.run_beam_search_jit(
            params, PG_HPS, arrays, loop="scan")
        np.testing.assert_array_equal(np.asarray(a.tokens),
                                      np.asarray(b.tokens))
        np.testing.assert_array_equal(np.asarray(a.avg_log_prob),
                                      np.asarray(b.avg_log_prob))

    def test_bf16_cache_drift_envelope(self):
        """End-to-end drift envelope: same params/articles decoded with
        the f32 and bf16 caches must agree to bf16 resolution — the
        searches emit valid trajectories whose per-article average
        log-prob drifts by < 2e-2 (bf16 has ~3 significant digits; the
        f32 softmax math keeps the rounding from compounding)."""
        a = self._outputs("float32")
        b = self._outputs("bfloat16")
        np.testing.assert_allclose(np.asarray(a.avg_log_prob),
                                   np.asarray(b.avg_log_prob), atol=2e-2)
        assert np.asarray(b.length).min() >= 2
        # attention rows remain distributions under the narrowed cache
        for row, n in zip(np.asarray(b.attn_dists),
                          np.asarray(b.length)):
            np.testing.assert_allclose(row[: n - 1].sum(axis=-1), 1.0,
                                       atol=1e-4)

    def test_bf16_cache_single_step_envelope(self):
        """One controlled adapter step, identical inputs, f32 vs bf16
        cache: top-2K log-probs and attention within bf16 tolerance (the
        direct storage-only claim, no search dynamics in the way)."""
        family = get_family("transformer")
        outs = {}
        for dtype in ("float32", "bfloat16"):
            hps = TF_HPS.replace(decode_cache_dtype=dtype)
            params = family.init_params(hps, hps.vocab_size,
                                        jax.random.PRNGKey(7))
            arrays = make_arrays(hps, seed=4)
            enc_view = family.beam_encode(params, hps, arrays)
            enc_one = jax.tree_util.tree_map(lambda x: x[0], enc_view)
            init_state_fn, step_fn = family.beam_adapter(hps)
            state = init_state_fn(params, enc_one)
            latest = jnp.full((hps.beam_size,), START_ID, jnp.int32)
            out = step_fn(params, enc_one,
                          jnp.asarray(arrays["enc_padding_mask"][0]),
                          jnp.asarray(arrays["enc_batch_extend_vocab"][0]),
                          jnp.int32(0), latest, state)
            outs[dtype] = out
        np.testing.assert_allclose(
            np.asarray(outs["bfloat16"].topk_log_probs),
            np.asarray(outs["float32"].topk_log_probs), atol=2e-2)
        np.testing.assert_allclose(np.asarray(outs["bfloat16"].attn_dist),
                                   np.asarray(outs["float32"].attn_dist),
                                   atol=1e-2)
        assert outs["bfloat16"].state["cache_k"].dtype == jnp.bfloat16
        assert outs["float32"].state["cache_k"].dtype == jnp.float32


def _ledger_call(reg):
    """Slots' kernel runner through the shared compile ledger."""
    def call(site, fn, *args, key=""):
        return profile_lib.compiled_call(reg, site, fn, *args, key=key)

    return call


def test_finalize_adds_at_most_one_compile_to_warm_set():
    """ISSUE 7 acceptance detail: the backtrack lives INSIDE
    unpack_slot_jit, so a fresh config still warms the slot engine with
    exactly four compiles (init/pack/step/unpack) — the finalize pass
    adds at most one executable (unpack's own), not a fifth kernel.
    Asserted through the shared compile ledger (obs/profile.py, ISSUE
    16): every kernel call routes through compiled_call, whose
    jit-cache diff IS the growth this test used to read by hand."""
    # a config no other test compiles, so cache deltas are attributable
    hps = PG_HPS.replace(max_oov_buckets=6, beam_size=2)
    family = get_family("pointer_generator")
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(1))
    arrays = make_arrays(hps, seed=8)
    with obs.use_registry(Registry()) as reg:
        eng = Slots(params, hps, arrays, slots=2,
                    call=_ledger_call(reg))
        eng.pack(0, {k: v[0:1] for k, v in arrays.items()})
        eng.step([True, False], 2)
        eng.unpack(0)
        stats = profile_lib.profiler_for(reg).compile_stats()
    growth = {site: st["compiles"] for site, st in stats.items()
              if site != "decode/prefill_jit"}
    assert growth == {"decode/init_slots_jit": 1,
                      "decode/pack_slot_jit": 1,
                      "decode/step_slots_jit": 1,
                      "decode/unpack_slot_jit": 1}, stats


def test_warm_set_is_four_plus_one_prefill_per_bucket():
    """The ISSUE 11 compile-count pin: a fresh config warms the engine
    with exactly FOUR decode compiles (init/pack/step/unpack — slot
    index, occupancy, and valid length all traced) plus ONE prefill
    compile per bucket actually used — and after that warm set, no
    occupancy pattern, slot choice, article length, or length MIX
    recompiles anything.  Asserted through the shared compile ledger
    (obs/profile.py, ISSUE 16): warm_set_size() is the 4 + one-per-
    bucket committed number, the per-bucket prefill keys are named, and
    the post-warm churn must land as ledger HITS, not compiles."""
    # a config no other test compiles, so cache deltas are attributable
    hps = PG_HPS.replace(max_oov_buckets=6, beam_size=2,
                         decode_enc_block=4, batch_size=3)
    family = get_family("pointer_generator")
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(2))
    arrays = _arrays_with_lens(hps, (2, 7, 12), seed=5)
    buckets = (4, 8, 12)
    with obs.use_registry(Registry()) as reg:
        prof = profile_lib.install_profiler(reg)
        for kernel in ("decode/init_slots_jit", "decode/pack_slot_jit",
                       "decode/step_slots_jit", "decode/unpack_slot_jit"):
            prof.set_compile_budget(kernel, 1)
        prof.set_compile_budget("decode/prefill_jit", len(buckets))
        eng = Slots(params, hps, arrays, slots=3, call=_ledger_call(reg))

        def pre_at(row, bucket):
            return eng.prefill(_one_at(arrays, row, bucket), key=bucket)

        for slot, bucket in enumerate(buckets):  # warm every bucket
            eng.pack(slot, pre_at(slot, bucket))
        eng.step([True, True, True], 2)
        eng.unpack(1)
        stats = prof.compile_stats()
        growth = {site: st["compiles"] for site, st in stats.items()}
        assert growth == {"decode/init_slots_jit": 1,
                          "decode/pack_slot_jit": 1,
                          "decode/step_slots_jit": 1,
                          "decode/unpack_slot_jit": 1,
                          "decode/prefill_jit": len(buckets)}, stats
        # the committed warm set: 4 decode kernels + one prefill/bucket
        assert prof.warm_set_size() == 4 + len(buckets)
        assert stats["decode/prefill_jit"]["keys"] == sorted(
            str(b) for b in buckets), stats
        # churn: different slots, buckets, occupancy patterns, length
        # mixes — every call must land as a ledger HIT
        eng.pack(1, pre_at(0, 4))
        eng.step([False, True, True], 2)
        eng.pack(0, pre_at(2, 8))
        eng.step([True, False, False], 2)
        eng.unpack(0)
        after = prof.compile_stats()
        assert prof.warm_set_size() == 4 + len(buckets), after
        churn_hits = sum(st["hits"] for st in after.values()) \
            - sum(st["hits"] for st in stats.values())
        assert churn_hits == 7, after  # 2 prefills + 2 packs + 2 steps + 1 unpack
        # within budget on every site => the storm trigger stayed silent
        assert profile_lib.profile_alerts(reg)["compile_storm"] is None


# -- paged resident state parity (ISSUE 20) --------------------------------
#
# The slot state's enc-axis leaves are pools of decode_enc_block-row
# pages addressed through a per-slot page table (data, not shape); the
# tests above run them over the default arena, these over a sized one.
# The mirror stays the FULL-WIDTH materialized search: exactness across
# page-boundary article lengths, arena-full backpressure, and
# harvest-then-reuse page recycling is the claim that paging changed
# the MEMORY story, not the numerics.

from textsummarization_on_flink_tpu.decode.arena import (  # noqa: E402
    ArenaExhaustedError,
    PageArena,
)

#: article lengths at the page-layout edge cases for block=4 on the
#: 12-wide test scale (b_max=3): exactly ONE full page, straddling a
#: page boundary (block+1), the minimal 1-token article, and the full
#: 3-page grid — packed together (mixed page-count occupancy).
_PAGED_LENS = (4, 5, 1, 12)


@pytest.mark.parametrize("family_name,hps", FAMILY_CASES)
def test_paged_kernels_match_mirror_at_page_boundaries(family_name, hps):
    """Mixed page-count occupancy through the PAGED slot path: each
    article allocated ceil(len/block) real arena pages (scratch fill
    beyond), decoded together through the page-table gather, and every
    trajectory must match the full-width materialized mirror
    token-exactly — including the article whose length is exactly one
    page and the one straddling a page boundary."""
    hps = hps.replace(batch_size=len(_PAGED_LENS), decode_enc_block=4)
    family = get_family(family_name)
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(3))
    arrays = _arrays_with_lens(hps, _PAGED_LENS, seed=6)
    slots = len(_PAGED_LENS)
    arena = PageArena(9)  # 1+2+1+3 pages needed of 9
    eng = Slots(params, hps, arrays, slots, pages=arena.capacity)
    for slot, true_len in enumerate(_PAGED_LENS):
        bucket = next(b for b in _DISAGG_BUCKETS if true_len <= b)
        eng.pack(slot, _one_at(arrays, slot, bucket),
                 arena.alloc(max(1, -(-true_len // eng.block))))
    assert arena.pages_in_use == 7
    np.testing.assert_array_equal(
        np.asarray(eng.state.enc_valid_len), np.asarray(_PAGED_LENS))
    done, _ = eng.drive()
    assert sorted(done) == list(range(slots))
    for b in range(slots):
        ref = materialized_search(params, hps, family, arrays, b)
        _assert_slot_matches_mirror(done[b], ref)


@pytest.mark.parametrize("family_name,hps", FAMILY_CASES)
def test_paged_arena_full_backpressure_then_recycle_exact(family_name,
                                                          hps):
    """The backpressure + recycling contract at the kernel level: with
    the arena sized for ONE full-length resident, the second admission's
    allocation fails TYPED and all-or-nothing (no pages leak, the
    resident is untouched); after the first article harvests and frees,
    the retried admission reuses the very same page ids in a DIFFERENT
    slot — and still decodes token-exactly against the mirror, proving
    recycled pages carry no ghost of their previous tenant (the
    harvested slot's stale table row routes to scratch, never to the
    reused pages)."""
    hps = hps.replace(batch_size=2, decode_enc_block=4)
    family = get_family(family_name)
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(3))
    arrays = _arrays_with_lens(hps, (12, 12), seed=6)
    arena = PageArena(3)  # exactly one 3-page resident fits
    eng = Slots(params, hps, arrays, 2, pages=arena.capacity)
    ids_a = arena.alloc(3)
    eng.pack(0, _one_at(arrays, 0, 12), ids_a)
    # the second full-length admission cannot get pages: typed, carries
    # the shortfall, allocates NOTHING
    with pytest.raises(ArenaExhaustedError) as exc:
        arena.alloc(3)
    assert exc.value.needed == 3 and exc.value.free == 0
    assert arena.free_pages == 0 and arena.pages_in_use == 3
    # drive the resident alone to completion — the blocked admission
    # never touched it
    done, _ = eng.drive([True, False])
    ref0 = materialized_search(params, hps, family, arrays, 0)
    _assert_slot_matches_mirror(done[0], ref0)
    # harvest frees the pages (drive pointed the stale row at scratch,
    # the engine contract); the retried admission reuses the SAME ids
    arena.free(ids_a.tolist())
    assert (eng.table[0] == arena.capacity).all()
    ids_b = arena.alloc(3)
    assert sorted(ids_b.tolist()) == sorted(ids_a.tolist())
    eng.pack(1, _one_at(arrays, 1, 12), ids_b)
    done, _ = eng.drive([False, True])
    ref1 = materialized_search(params, hps, family, arrays, 1)
    _assert_slot_matches_mirror(done[1], ref1)


def test_paged_warm_set_allocation_churn_never_recompiles():
    """The ISSUE 20 compile pin: over a sized arena the engine warms
    with the SAME four decode compiles (page-table contents, allocation pattern,
    page-count mix, and occupancy are all traced data) plus one prefill
    per bucket — and after the warm set, page recycling, permuted
    allocation orders, different page counts per slot, and table
    rewrites all land as ledger HITS, never compiles."""
    # max_oov_buckets=5 keeps every aval distinct from the default-arena
    # warm-set tests above, so the ledger counts FRESH compiles even in
    # a shared-process run (the global jit caches persist across tests)
    hps = PG_HPS.replace(max_oov_buckets=5, beam_size=2,
                         decode_enc_block=4, batch_size=3)
    family = get_family("pointer_generator")
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(2))
    arrays = _arrays_with_lens(hps, (2, 7, 12), seed=5)
    buckets = (4, 8, 12)
    with obs.use_registry(Registry()) as reg:
        prof = profile_lib.install_profiler(reg)
        for kernel in ("decode/init_slots_jit", "decode/pack_slot_jit",
                       "decode/step_slots_jit", "decode/unpack_slot_jit"):
            prof.set_compile_budget(kernel, 1)
        prof.set_compile_budget("decode/prefill_jit", len(buckets))

        eng = Slots(params, hps, arrays, slots=3, pages=7,
                    call=_ledger_call(reg))

        def pack(slot, bucket, ids):
            eng.pack(slot, eng.prefill(_one_at(arrays, slot, bucket),
                                       key=bucket), ids)

        # warm: every bucket, differing page counts (1, 2, 3 pages)
        pack(0, 4, [0])
        pack(1, 8, [1, 2])
        pack(2, 12, [3, 4, 5])
        eng.step([True, True, True], 2)
        eng.unpack(1)
        stats = prof.compile_stats()
        growth = {site: st["compiles"] for site, st in stats.items()}
        assert growth == {"decode/init_slots_jit": 1,
                          "decode/pack_slot_jit": 1,
                          "decode/step_slots_jit": 1,
                          "decode/unpack_slot_jit": 1,
                          "decode/prefill_jit": len(buckets)}, stats
        assert prof.warm_set_size() == 4 + len(buckets)
        # allocation-pattern churn: recycled ids out of order, a
        # different page count in the same slot, a non-contiguous
        # allocation, shifting occupancy — all HITS
        pack(1, 4, [6])                    # fewer pages, new id
        pack(0, 8, [5, 1])                 # recycled, permuted
        eng.step([True, False, True], 2)
        pack(2, 12, [2, 0, 4])             # recycled, shuffled
        eng.step([False, True, True], 2)
        eng.unpack(2)
        after = prof.compile_stats()
        assert prof.warm_set_size() == 4 + len(buckets), after
        churn_hits = sum(st["hits"] for st in after.values()) \
            - sum(st["hits"] for st in stats.values())
        assert churn_hits == 9, after  # 3 prefills + 3 packs + 2 steps + 1 unpack
        assert profile_lib.profile_alerts(reg)["compile_storm"] is None


class TestPageArena:
    """The host allocator's contract: LIFO reuse, all-or-nothing
    allocation, loud double-free."""

    def test_alloc_free_roundtrip_and_fill(self):
        a = PageArena(4)
        ids = a.alloc(3)
        assert sorted(ids.tolist()) == [0, 1, 2]
        assert (a.capacity, a.free_pages, a.pages_in_use) == (4, 1, 3)
        assert a.fill == 0.75
        a.free(ids.tolist())
        assert a.free_pages == 4 and a.fill == 0.0

    def test_alloc_is_all_or_nothing(self):
        a = PageArena(4)
        a.alloc(3)
        with pytest.raises(ArenaExhaustedError) as exc:
            a.alloc(2)
        assert exc.value.needed == 2 and exc.value.free == 1
        assert a.free_pages == 1  # the failed alloc took nothing

    def test_lifo_reuse(self):
        a = PageArena(4)
        first = a.alloc(2)
        a.free(first.tolist())
        again = a.alloc(2)
        assert sorted(again.tolist()) == sorted(first.tolist())

    def test_double_free_and_bad_ids_raise(self):
        a = PageArena(2)
        ids = a.alloc(1)
        a.free(ids.tolist())
        with pytest.raises(ValueError):
            a.free(ids.tolist())
        with pytest.raises(ValueError):
            a.free([7])
        with pytest.raises(ValueError):
            PageArena(0)


# -- the arena an engine gets (ISSUE 32) ------------------------------------
#
# config.resolve_arena_pages is the one rule: the pages asked for, else
# the pages a byte budget buys, else every slot at full length — never
# zero, never fewer than one full-length article.

_ARENA_HPS = PG_HPS.replace(decode_enc_block=4)  # b_max = 3


@pytest.mark.parametrize("options,slots,page_bytes,want", [
    pytest.param({}, 5, None, 15, id="no-option-is-slots-x-b_max"),
    pytest.param({"serve_arena_pages": 7}, 5, None, 7, id="explicit-pages"),
    pytest.param({"serve_arena_mb": 1.0}, 5, 4096, 256, id="byte-budget"),
    pytest.param({"serve_arena_pages": 2}, 5, None, ValueError,
                 id="under-one-article-raises"),
    pytest.param({"serve_arena_mb": 0.01}, 5, 4096, ValueError,
                 id="budget-under-one-article-raises"),
    pytest.param({"serve_arena_mb": 1.0}, 5, None, ValueError,
                 id="budget-needs-page-bytes"),
])
def test_resolve_arena_pages(options, slots, page_bytes, want):
    hps = _ARENA_HPS.replace(**options)
    if want is ValueError:
        with pytest.raises(ValueError):
            resolve_arena_pages(hps, slots, page_bytes)
    else:
        assert resolve_arena_pages(hps, slots, page_bytes) == want


_WORDS = ("the a cat dog sat ran mat home big small quick brown fox "
          "jumped over lazy it was day night").split()


def _engine_and_examples(hps, params, tmp_path, lens, slots):
    """A SlotDecodeEngine over `params` and one SummaryExample an entry
    of `lens` (article lengths in words)."""
    from textsummarization_on_flink_tpu.data.batching import SummaryExample
    from textsummarization_on_flink_tpu.data.vocab import Vocab
    from textsummarization_on_flink_tpu.decode.decoder import (
        BeamSearchDecoder,
    )

    vocab = Vocab(words=_WORDS)
    assert vocab.size() == hps.vocab_size
    rng = np.random.RandomState(4)
    exs = [SummaryExample.build(" ".join(rng.choice(_WORDS, n)), [], vocab,
                                hps, uuid=f"u{i}")
           for i, n in enumerate(lens)]
    dec = BeamSearchDecoder(hps, vocab, batcher=None, params=params,
                            decode_root=str(tmp_path / "d"))
    return dec, dec.slot_engine(slots=slots, chunk=3), exs, vocab


def _drain(eng, exs_by_slot):
    results = {}
    for _ in range(16):
        for idx in eng.step():
            results[idx] = eng.unpack(idx, exs_by_slot[idx])
        if len(results) == len(exs_by_slot):
            return results
    raise AssertionError("engine never drained")


def test_engine_with_no_arena_option_holds_every_slot_at_full_length(
        tmp_path):
    """Every slot filled with a full-length article: the default arena
    has exactly the pages, nothing waits, and it drains to zero."""
    hps = _ARENA_HPS
    assert hps.serve_arena_pages == 0 and hps.serve_arena_mb == 0
    params = get_family(hps.model_family).init_params(
        hps, hps.vocab_size, jax.random.PRNGKey(3))
    slots = 3
    _, eng, exs, _ = _engine_and_examples(
        hps, params, tmp_path, [hps.max_enc_steps + 5] * slots, slots)
    assert eng.arena_stats()["capacity"] == slots * 3
    for i, ex in enumerate(exs):
        assert eng.pages_needed(ex) == 3
        eng.pack(i, ex)  # no ArenaExhaustedError
    assert eng.free_pages() == 0 and eng.arena_stats()["fill"] == 1.0
    _drain(eng, dict(enumerate(exs)))
    stats = eng.arena_stats()
    assert stats["in_use"] == 0 and stats["free"] == stats["capacity"]


@pytest.mark.parametrize("family_name,hps", FAMILY_CASES)
def test_default_arena_engine_matches_the_batch_search(family_name, hps,
                                                       tmp_path):
    """Continuous decode with no arena option, mixed lengths and a slot
    reused, against run_beam_search on the same articles."""
    from textsummarization_on_flink_tpu.data.batching import Batch

    hps = hps.replace(decode_enc_block=4, batch_size=4)
    params = get_family(family_name).init_params(hps, hps.vocab_size,
                                                 jax.random.PRNGKey(3))
    dec, eng, exs, vocab = _engine_and_examples(
        hps, params, tmp_path, (1, 4, 9, 12), slots=3)
    want = dec.decode_batch(Batch(exs, hps, vocab))
    for i in range(3):
        eng.pack(i, exs[i])
    got = _drain(eng, dict(enumerate(exs[:3])))
    eng.pack(1, exs[3])  # a retired slot's pages again
    got[3] = _drain(eng, {1: exs[3]})[1]
    for i, w in enumerate(want):
        assert got[i].decoded_words == w.decoded_words
        assert got[i].avg_log_prob == pytest.approx(w.avg_log_prob,
                                                    rel=2e-5)
    assert eng.arena_stats()["in_use"] == 0
