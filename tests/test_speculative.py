"""Speculative decode tier (ISSUE 10): token-exactness with full-model
greedy decode for BOTH families' verify paths (the transformer's
parallel verify and the adapter-scan fallback), in BOTH disagreement
directions (accept-all and reject-at-0), acceptance-distribution
determinism, compile-once across acceptance patterns, the AAN family's
train/decode consistency and checkpoint-mapped bootstrap, the serving
quality tiers end to end over a real tiny model, and the spec-resident
dispatch-fault chaos contract.

(The AAN beam-adapter parity through all four loop kinds lives in
test_beam_backtrack.py — the family rides the same materialized-history
mirror as the other two.)
"""

import numpy as np
import jax
import pytest

from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.config import HParams, derive_draft_hps
from textsummarization_on_flink_tpu.data.vocab import STOP_ID, Vocab
from textsummarization_on_flink_tpu.decode import beam_search, speculative
from textsummarization_on_flink_tpu.decode.decoder import BeamSearchDecoder
from textsummarization_on_flink_tpu.models import avg_attention, get_family
from textsummarization_on_flink_tpu.obs import Registry
from textsummarization_on_flink_tpu.obs import profile as profile_lib
from textsummarization_on_flink_tpu.serve.server import ServingServer

TF_HPS = HParams(batch_size=3, hidden_dim=8, emb_dim=8, vocab_size=24,
                 max_enc_steps=12, max_dec_steps=8, beam_size=3,
                 min_dec_steps=2, max_oov_buckets=4, mode="decode",
                 model_family="transformer", num_heads=2, enc_layers=2,
                 dec_layers=2, spec_k=3, draft_dec_layers=1)
PG_HPS = TF_HPS.replace(model_family="pointer_generator", emb_dim=6,
                        draft_dec_layers=0)
AAN_HPS = TF_HPS.replace(model_family="avg_attention", draft_dec_layers=0)

FAMILY_CASES = [
    pytest.param(TF_HPS, id="tf-parallel-verify"),
    pytest.param(PG_HPS, id="pg-scan-verify"),
]


@pytest.fixture(autouse=True)
def _isolated_obs():
    with obs.use_registry(Registry()) as reg:
        yield reg


def make_arrays(hps, B, seed=0):
    rng = np.random.RandomState(seed)
    T_enc = hps.max_enc_steps
    enc_lens = rng.randint(T_enc // 2, T_enc + 1, size=(B,)).astype(np.int32)
    mask = (np.arange(T_enc)[None, :] < enc_lens[:, None]).astype(np.float32)
    enc = (rng.randint(0, hps.vocab_size, size=(B, T_enc))
           * mask).astype(np.int32)
    ext = enc.copy()
    oov = rng.rand(B, T_enc) < 0.1
    ext[oov] = hps.vocab_size + rng.randint(0, hps.max_oov_buckets,
                                            size=int(oov.sum()))
    return {"enc_batch": enc, "enc_lens": enc_lens,
            "enc_padding_mask": mask,
            "enc_batch_extend_vocab": ext.astype(np.int32)}


def make_models(hps, seed=0):
    family = get_family(hps.model_family)
    params = family.init_params(hps, hps.vocab_size,
                                jax.random.PRNGKey(seed))
    dhps = derive_draft_hps(hps)
    if hps.model_family == "transformer":
        draft = avg_attention.init_from_transformer(
            params, hps, dhps, jax.random.PRNGKey(seed + 1))
    else:
        draft = avg_attention.init_params(dhps, hps.vocab_size,
                                          jax.random.PRNGKey(seed + 1))
    return params, draft


def assert_spec_matches_greedy(params, draft, hps, arrays):
    """spec output == beam_size=1 beam search (the serving ladder's
    greedy tier) token for token, plus attention/p_gen/score parity."""
    greedy = beam_search.run_beam_search(params, hps.replace(beam_size=1),
                                         arrays)
    spec = speculative.run_spec_decode(params, draft, hps, arrays)
    B = arrays["enc_batch"].shape[0]
    for b in range(B):
        n, ns = int(greedy.length[b]), int(spec.length[b])
        assert n == ns, f"row {b}: greedy len {n} != spec len {ns}"
        gt = list(np.asarray(greedy.tokens[b])[:n])
        st = list(np.asarray(spec.tokens[b])[:n])
        assert gt == st, f"row {b}: {gt} != {st}"
        np.testing.assert_allclose(spec.avg_log_prob[b],
                                   greedy.avg_log_prob[b],
                                   rtol=1e-5, atol=1e-6)
        gen = n - 1
        np.testing.assert_allclose(np.asarray(spec.attn_dists[b])[:gen],
                                   np.asarray(greedy.attn_dists[b])[:gen],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(spec.p_gens[b])[:gen],
                                   np.asarray(greedy.p_gens[b])[:gen],
                                   rtol=1e-5, atol=1e-6)
    return spec


# -- token exactness --------------------------------------------------------

@pytest.mark.parametrize("hps", FAMILY_CASES)
def test_spec_token_exact_with_greedy(hps):
    """The headline contract: whatever the draft proposes, the emitted
    stream equals full-model greedy decode (several seeds so the
    accept/reject mix varies)."""
    params, draft = make_models(hps)
    for seed in (0, 1, 2):
        assert_spec_matches_greedy(params, draft, hps,
                                   make_arrays(hps, 3, seed=seed))


def test_spec_exact_under_accept_all():
    """Disagreement direction 1: a PERFECT draft (the full model used
    as its own draft — avg_attention full, identical draft params)
    accepts every proposal, and the output is still exactly greedy."""
    hps = AAN_HPS
    family = get_family(hps.model_family)
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(0))
    arrays = make_arrays(hps, 3)
    spec = assert_spec_matches_greedy(params, params, hps, arrays)
    # every cycle accepted all spec_k proposals
    np.testing.assert_array_equal(spec.accepted, spec.drafted)
    assert int(spec.accept_hist[:, : hps.spec_k].sum()) == 0


def test_spec_exact_under_reject_at_0():
    """Disagreement direction 2: an adversarial draft that always
    proposes one fixed token is rejected at position 0 every cycle —
    one corrected token per cycle, still exactly greedy."""
    hps = TF_HPS
    params, draft = make_models(hps)
    # slam the draft's output bias so it proposes token 7 always; make
    # sure the FULL model never greedily picks 7 by biasing it away
    draft = dict(draft)
    draft["out_bias"] = draft["out_bias"].at[7].set(1e4)
    params = dict(params)
    params["out_bias"] = params["out_bias"].at[7].set(-1e4)
    # ...and pin both models' p_gen to 1: through the pointer mixture
    # the draft can still COPY some other token past its vocabulary
    # bias (and the full model copy a 7), and which side wins that
    # near-tie depends on the host's CPU codegen
    for model in (draft, params):
        model["pgen_linear"] = dict(
            model["pgen_linear"],
            bias=model["pgen_linear"]["bias"] + 1e4)
    arrays = make_arrays(hps, 3)
    spec = assert_spec_matches_greedy(params, draft, hps, arrays)
    assert int(spec.accepted.sum()) == 0
    # one emitted token per cycle: cycles == generated token count
    np.testing.assert_array_equal(spec.cycles,
                                  np.asarray(spec.length) - 1)
    np.testing.assert_array_equal(spec.accept_hist[:, 0], spec.cycles)
    assert int(spec.accept_hist[:, 1:].sum()) == 0


# -- determinism + compile discipline ---------------------------------------

def test_spec_acceptance_distribution_deterministic():
    """Fixed seeds in, identical acceptance-length distribution out —
    twice (the speculative loop has no hidden RNG or host state)."""
    hps = TF_HPS
    params, draft = make_models(hps)
    arrays = make_arrays(hps, 3, seed=5)
    one = speculative.run_spec_decode(params, draft, hps, arrays)
    two = speculative.run_spec_decode(params, draft, hps, arrays)
    np.testing.assert_array_equal(one.accept_hist, two.accept_hist)
    np.testing.assert_array_equal(one.tokens, two.tokens)
    np.testing.assert_array_equal(one.cycles, two.cycles)


def test_spec_compiles_once_across_acceptance_patterns(_isolated_obs):
    """Traced accept length (the step_slots_jit discipline): articles
    with different accept/reject patterns — including the adversarial
    reject-everything draft — share ONE compiled program.  Asserted
    through the shared compile ledger (obs/profile.py, ISSUE 16): the
    ledger's per-site miss/hit counts ARE the jit-cache diffs this test
    used to read off run_spec_decode_jit._cache_size() by hand."""
    hps = TF_HPS
    params, draft = make_models(hps)
    jax.clear_caches()  # the ledger counts MISSES; start from cold
    for seed in range(4):
        speculative.run_spec_decode(params, draft, hps,
                                    make_arrays(hps, 3, seed=seed))
    bad_draft = dict(draft)
    bad_draft["out_bias"] = bad_draft["out_bias"].at[7].set(1e4)
    speculative.run_spec_decode(params, bad_draft, hps,
                                make_arrays(hps, 3, seed=9))
    prof = profile_lib.profiler_for(_isolated_obs)
    site = prof.compile_stats()["decode/spec_decode_jit"]
    assert site["compiles"] == 1, (
        "speculative decode recompiled across acceptance patterns: "
        f"{site}")
    assert site["hits"] == 4, site
    assert site["keys"] == [str(int(hps.spec_k))], site


# -- acceptance-adaptive spec_k (ISSUE 12) ----------------------------------

def _budget_adaptive():
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..",
                        "BYTE_BUDGET.json")
    with open(path) as f:
        return json.load(f)["spec"]["adaptive"]


class TestAdaptiveSpecK:
    def test_k_never_leaves_committed_bounds(self):
        """Property: whatever histogram stream arrives, k stays in
        [k_min, k_max] (including degenerate all-zero deltas)."""
        ctl = speculative.SpecKController(2, 3, 6, draft_ratio=0.25)
        rng = np.random.RandomState(0)
        for _ in range(200):
            k = ctl.k
            hist = rng.randint(0, 5, size=k + 1)
            if rng.rand() < 0.2:
                hist[:] = 0
            ctl.observe(hist, k)
            assert 2 <= ctl.k <= 6, ctl.k

    def test_trajectory_pinned_deterministic(self):
        """The committed BYTE_BUDGET.json spec.adaptive trajectories:
        the k walk under fixed accept sequences at the committed draft
        ratio is EXACTLY the pinned one, twice (no hidden state, no
        RNG, no clock)."""
        ad = _budget_adaptive()
        cases = {
            "accept_all_trajectory": lambda k, n: [0] * k + [n],
            "reject_at_0_trajectory": lambda k, n: [n] + [0] * k,
            "half_accept_trajectory":
                lambda k, n: [0] * (k // 2) + [n] + [0] * (k - k // 2),
        }
        per = int(ad["cycles_per_round"])
        for name, hist_fn in cases.items():
            want = ad[name]
            for _attempt in range(2):
                ctl = speculative.SpecKController(
                    int(ad["k_min"]), int(ad["k_start"]),
                    int(ad["k_max"]), float(ad["draft_ratio"]))
                got = []
                for _ in range(len(want)):
                    got.append(ctl.observe(hist_fn(ctl.k, per), ctl.k))
                assert got == want, (name, got, want)

    def test_adaptive_exact_and_converges_up_under_accept_all(self):
        """The self-draft harness (perfect draft): output stays exactly
        greedy with k adapting, and over enough batches the controller
        climbs to spec_k_max."""
        hps = AAN_HPS.replace(spec_k_adaptive=True, spec_k=2,
                              spec_k_min=1, spec_k_max=6)
        hps.validate()
        family = get_family(hps.model_family)
        params = family.init_params(hps, hps.vocab_size,
                                    jax.random.PRNGKey(0))
        ctl = speculative.SpecKController.from_hps(hps, draft_ratio=0.25)
        for seed in range(6):
            arrays = make_arrays(hps, 3, seed=seed)
            greedy = beam_search.run_beam_search(
                params, hps.replace(beam_size=1), arrays)
            out = speculative.run_spec_decode(params, params, hps,
                                              arrays, controller=ctl)
            for b in range(3):
                n = int(greedy.length[b])
                assert n == int(out.length[b])
                assert (list(np.asarray(greedy.tokens[b])[:n])
                        == list(np.asarray(out.tokens[b])[:n]))
        assert ctl.k == hps.spec_k_max, (ctl.k, ctl.alpha)

    def test_adaptive_exact_and_converges_down_under_reject_at_0(self):
        """The adversarial out_bias harness (always-rejected draft):
        output stays exactly greedy and the controller settles at
        spec_k_min — never paying more than the minimum draft steps
        for zero expected acceptance."""
        hps = TF_HPS.replace(spec_k_adaptive=True, spec_k=3,
                             spec_k_min=1, spec_k_max=5)
        hps.validate()
        params, draft = make_models(hps)
        draft = dict(draft)
        draft["out_bias"] = draft["out_bias"].at[7].set(1e4)
        params = dict(params)
        params["out_bias"] = params["out_bias"].at[7].set(-1e4)
        ctl = speculative.SpecKController.from_hps(hps, draft_ratio=0.25)
        for seed in range(3):
            arrays = make_arrays(hps, 3, seed=seed)
            greedy = beam_search.run_beam_search(
                params, hps.replace(beam_size=1), arrays)
            out = speculative.run_spec_decode(params, draft, hps,
                                              arrays, controller=ctl)
            for b in range(3):
                n = int(greedy.length[b])
                assert n == int(out.length[b])
                assert (list(np.asarray(greedy.tokens[b])[:n])
                        == list(np.asarray(out.tokens[b])[:n]))
        # (acceptance is NEAR zero, not exactly zero: on some articles
        # the pointer COPY path re-ranks token 7 into the full model's
        # greedy choice despite the vocab bias — the zero-acceptance
        # direction itself is pinned by test_spec_exact_under_reject_at_0)
        assert ctl.k == hps.spec_k_min, (ctl.k, ctl.alpha)

    def test_warm_set_bounded_one_compile_per_distinct_k(
            self, _isolated_obs):
        """The compile discipline: the cycle kernel compiles once per
        DISTINCT k the controller visits (carry shapes ride spec_k_max,
        so k changes never reshape), and repeats at a warm k add
        nothing.  Asserted through the shared compile ledger
        (obs/profile.py, ISSUE 16), whose per-k keys also pin WHICH k's
        compiled — and whose committed budget (one kernel per k in
        [k_min, k_max]) must not have fired a compile storm."""
        hps = TF_HPS.replace(spec_k_adaptive=True, spec_k=3,
                             spec_k_min=1, spec_k_max=5)
        hps.validate()
        params, draft = make_models(hps)
        jax.clear_caches()  # the ledger counts MISSES; start from cold
        ks_seen = set()

        class Spy(speculative.SpecKController):
            def update(self):
                super().update()
                ks_seen.add(self.k)
                return self.k

        ctl = Spy(hps.spec_k_min, hps.spec_k, hps.spec_k_max,
                  draft_ratio=0.25)
        ks_seen.add(ctl.k)
        for seed in range(4):
            speculative.run_spec_decode(params, draft, hps,
                                        make_arrays(hps, 3, seed=seed),
                                        controller=ctl)
        prof = profile_lib.profiler_for(_isolated_obs)
        site = prof.compile_stats()["decode/spec_cycle_jit"]
        budget = hps.spec_k_max - hps.spec_k_min + 1
        assert site["compiles"] == len(ks_seen), (site, sorted(ks_seen))
        assert site["keys"] == sorted(str(k) for k in ks_seen), site
        assert site["compiles"] <= budget
        assert site["budget"] == budget, site
        # within budget => the storm trigger stayed silent
        assert profile_lib.profile_alerts(
            _isolated_obs)["compile_storm"] is None

    def test_decoder_accept_hist_buckets_span_k_max(self, _isolated_obs):
        """The ISSUE-12 satellite fix: the accept-length histogram's
        buckets cover 0..spec_k_max (resolve_spec_bounds), so adaptive
        cycles at k > spec_k can't pile into one overflow bin."""
        import tempfile

        hps = serve_hps(spec_k_adaptive=True, spec_k=2, spec_k_min=1,
                        spec_k_max=7)
        family = get_family(hps.model_family)
        params = family.init_params(hps, hps.vocab_size,
                                    jax.random.PRNGKey(0))
        decoder = BeamSearchDecoder(
            hps, serve_vocab(), batcher=None, params=params,
            decode_root=tempfile.mkdtemp(prefix="spec_bkt_"))
        assert decoder._h_accept.buckets == tuple(
            float(i) for i in range(0, hps.spec_k_max + 1))
        assert decoder._spec_ctl is not None
        assert decoder._spec_ctl.k == hps.spec_k


# -- AAN family: train/decode consistency + mapped bootstrap ----------------

class TestAvgAttentionFamily:
    def test_train_decode_consistency(self):
        """Teacher-forced forward_train and the O(1) decode step agree
        on the same forced tokens (cumsum vs running-sum only differ in
        summation order -> tight tolerance, not bitwise)."""
        hps = AAN_HPS.replace(batch_size=2, mode="train")
        family = get_family("avg_attention")
        params = family.init_params(hps, hps.vocab_size,
                                    jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        T_enc, T_dec = hps.max_enc_steps, hps.max_dec_steps
        arrays = make_arrays(hps, 2)
        dec = rng.randint(1, hps.vocab_size, size=(2, T_dec)).astype(np.int32)
        arrays.update({
            "dec_batch": dec,
            "target_batch": np.roll(dec, -1, axis=1),
            "dec_padding_mask": np.ones((2, T_dec), np.float32),
        })
        out = family.forward_train(params, hps, arrays)
        assert np.isfinite(float(out.total_loss))
        # decode path: feed the same forced tokens through the adapter
        enc_view = family.beam_encode(params, hps, arrays)
        init_fn, step_fn = family.beam_adapter(hps.replace(beam_size=1))
        for b in range(2):
            enc_one = jax.tree_util.tree_map(lambda x, b=b: x[b], enc_view)
            state = init_fn(params, enc_one)
            for t in range(T_dec):
                step = step_fn(params, enc_one,
                               arrays["enc_padding_mask"][b],
                               arrays["enc_batch_extend_vocab"][b],
                               np.int32(t), dec[b, t:t + 1], state)
                state = step.state
                np.testing.assert_allclose(
                    np.asarray(step.attn_dist[0]),
                    np.asarray(out.attn_dists[b, t]),
                    rtol=1e-4, atol=1e-5)
                np.testing.assert_allclose(
                    float(step.p_gen[0]), float(out.p_gens[b, t]),
                    rtol=1e-4, atol=1e-5)

    def test_mapped_bootstrap_copies_shared_leaves(self):
        hps = TF_HPS.replace(dec_layers=4, draft_dec_layers=2)
        full = get_family("transformer").init_params(
            hps, hps.vocab_size, jax.random.PRNGKey(0))
        dhps = derive_draft_hps(hps)
        draft = avg_attention.init_from_transformer(
            full, hps, dhps, jax.random.PRNGKey(1))
        np.testing.assert_array_equal(draft["embedding"],
                                      full["embedding"])
        np.testing.assert_array_equal(draft["out_bias"], full["out_bias"])
        assert len(draft["decoder"]["layers"]) == 2
        # evenly strided subset keeps first and last full layers
        keep = avg_attention.draft_layer_indices(4, 2)
        assert keep == [0, 3]
        for dst, src_idx in zip(draft["decoder"]["layers"], keep):
            src = full["decoder"]["layers"][src_idx]
            np.testing.assert_array_equal(dst["cross_attn"]["wq"],
                                          src["cross_attn"]["wq"])
            np.testing.assert_array_equal(dst["ffn"]["w1"],
                                          src["ffn"]["w1"])
            assert "aan_ffn" in dst and "aan_gate" in dst

    def test_narrow_mapped_bootstrap_shares_encoder_only(self):
        """The ISSUE-12 narrow variant: shared H-wide leaves copied
        verbatim (embedding, encoder, out_bias), the H_d decoder side
        fresh (emb_proj adapter, factored vocab_head, H_d blocks) —
        and the spec output is STILL exactly greedy (exactness never
        depended on draft quality)."""
        hps = TF_HPS.replace(draft_hidden=4, draft_vocab_rank=4)
        hps.validate()
        full = get_family("transformer").init_params(
            hps, hps.vocab_size, jax.random.PRNGKey(0))
        dhps = derive_draft_hps(hps)
        draft = avg_attention.init_from_transformer(
            full, hps, dhps, jax.random.PRNGKey(1))
        np.testing.assert_array_equal(draft["embedding"],
                                      full["embedding"])
        np.testing.assert_array_equal(
            draft["encoder"]["layers"][0]["ffn"]["w1"],
            full["encoder"]["layers"][0]["ffn"]["w1"])
        assert draft["emb_proj"]["kernel"].shape == (hps.hidden_dim, 4)
        assert draft["vocab_head"]["w1"].shape == (4, 4)
        assert draft["vocab_head"]["w2"].shape == (4, hps.vocab_size)
        layer = draft["decoder"]["layers"][0]
        assert layer["cross_attn"]["wk"].shape == (hps.hidden_dim, 4)
        assert layer["cross_attn"]["wq"].shape == (4, 4)
        assert_spec_matches_greedy(full, draft, hps,
                                   make_arrays(hps, 3))
        # fresh narrow init keeps exactness too (the other init mode)
        fresh = avg_attention.init_params(dhps, hps.vocab_size,
                                          jax.random.PRNGKey(2))
        assert_spec_matches_greedy(full, fresh, hps,
                                   make_arrays(hps, 3, seed=1))

    def test_narrow_draft_requires_factored_head(self):
        with pytest.raises(ValueError, match="factored vocab head"):
            TF_HPS.replace(draft_hidden=4).validate()

    def test_mapped_bootstrap_rejects_non_transformer(self):
        hps = PG_HPS
        params = get_family("pointer_generator").init_params(
            hps, hps.vocab_size, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="transformer checkpoints"):
            avg_attention.init_from_transformer(
                params, hps, derive_draft_hps(hps), jax.random.PRNGKey(1))

    def test_trainable(self):
        """The family trains through the shared loss head: finite loss,
        finite grads on both AAN-specific and shared leaves."""
        hps = AAN_HPS.replace(batch_size=2, mode="train", loss_chunk=4)
        family = get_family("avg_attention")
        params = family.init_params(hps, hps.vocab_size,
                                    jax.random.PRNGKey(0))
        arrays = make_arrays(hps, 2)
        rng = np.random.RandomState(1)
        T_dec = hps.max_dec_steps
        dec = rng.randint(1, hps.vocab_size, size=(2, T_dec)).astype(np.int32)
        arrays.update({"dec_batch": dec,
                       "target_batch": np.roll(dec, -1, axis=1),
                       "dec_padding_mask": np.ones((2, T_dec), np.float32)})

        def loss_fn(p):
            return family.forward_train(p, hps, arrays).total_loss

        grads = jax.grad(loss_fn)(params)
        flat = jax.tree_util.tree_leaves(grads)
        assert all(np.all(np.isfinite(np.asarray(g))) for g in flat)
        aan_g = grads["decoder"]["layers"][0]["aan_gate"]["kernel"]
        assert float(np.abs(np.asarray(aan_g)).sum()) > 0


# -- decoder + serving tiers over a real tiny model -------------------------

def serve_vocab():
    return Vocab(words=["the", "a", "cat", "dog", "sat", "ran", "mat",
                        "it", "was", "."])


def serve_hps(**kw):
    base = dict(mode="decode", batch_size=3, hidden_dim=8, emb_dim=8,
                vocab_size=16, max_enc_steps=12, max_dec_steps=6,
                beam_size=2, min_dec_steps=1, max_oov_buckets=4,
                model_family="transformer", num_heads=2, enc_layers=1,
                dec_layers=2, spec_k=2, draft_dec_layers=1,
                spec_draft="map", serve_max_wait_ms=50.0,
                serve_max_queue=32)
    base.update(kw)
    hps = HParams(**base)
    hps.validate()
    return hps


class TestServingTiers:
    def _server(self, reg, **kw):
        hps = serve_hps(**kw)
        vocab = serve_vocab()
        family = get_family(hps.model_family)
        params = family.init_params(hps, vocab.size(),
                                    jax.random.PRNGKey(0))
        import tempfile

        decoder = BeamSearchDecoder(
            hps, vocab, batcher=None, params=params,
            decode_root=tempfile.mkdtemp(prefix="spec_tier_"))
        return ServingServer(hps, vocab, decoder=decoder, registry=reg), \
            decoder

    def test_spec_tier_matches_greedy_tier_rows(self, _isolated_obs):
        server, _ = self._server(_isolated_obs)
        with server:
            greedy = [server.submit(f"the cat sat {i} .", uuid=f"g{i}",
                                    tier="greedy").result(timeout=600)
                      for i in range(3)]
            spec = [server.submit(f"the cat sat {i} .", uuid=f"s{i}",
                                  tier="spec").result(timeout=600)
                    for i in range(3)]
        for g, s in zip(greedy, spec):
            assert g.decoded_words == s.decoded_words, (g.uuid, s.uuid)
            assert s.tier == "spec" and g.tier == "greedy"
        assert _isolated_obs.counter("serve/tier_spec_total").value == 3
        assert _isolated_obs.counter("serve/tier_greedy_total").value == 3
        assert _isolated_obs.counter(
            "decode/spec_cycles_total").value > 0

    def test_spec_tier_adaptive_serves_exact_rows(self, _isolated_obs):
        """The adaptive controller through the FULL serving surface:
        spec-tier rows stay identical to greedy-tier rows, the decoder
        holds one persistent controller across requests, and its pick
        is exported on the decode/spec_k_current gauge."""
        server, decoder = self._server(_isolated_obs,
                                       spec_k_adaptive=True, spec_k=2,
                                       spec_k_min=1, spec_k_max=4)
        with server:
            greedy = [server.submit(f"the cat sat {i} .", uuid=f"g{i}",
                                    tier="greedy").result(timeout=600)
                      for i in range(2)]
            spec = [server.submit(f"the cat sat {i} .", uuid=f"s{i}",
                                  tier="spec").result(timeout=600)
                    for i in range(2)]
        for g, s in zip(greedy, spec):
            assert g.decoded_words == s.decoded_words, (g.uuid, s.uuid)
        ctl = decoder._spec_ctl
        assert ctl is not None and ctl.cycles > 0
        assert 1 <= ctl.k <= 4
        assert _isolated_obs.gauge(
            "decode/spec_k_current").value == float(ctl.k)

    def test_draft_tier_serves_and_counts(self, _isolated_obs):
        server, _ = self._server(_isolated_obs)
        with server:
            res = server.submit("the dog ran .", uuid="d0",
                                tier="draft").result(timeout=600)
        assert res.tier == "draft"
        assert _isolated_obs.counter("serve/tier_draft_total").value == 1

    def test_tier_validation_at_submit(self, _isolated_obs):
        server, _ = self._server(_isolated_obs, spec_draft="")
        with server:
            with pytest.raises(ValueError, match="one of"):
                server.submit("the cat .", tier="warp")
            with pytest.raises(ValueError, match="draft model"):
                server.submit("the cat .", tier="spec")

    def test_spec_resident_dispatch_fault_typed_exactly_once(
            self, _isolated_obs):
        """Chaos (ISSUE 10 satellite): an injected serve.dispatch fault
        while spec-tier requests are resident fails THOSE requests with
        the typed cause, each exactly once; the server lives on and the
        next spec request serves."""
        server, _ = self._server(_isolated_obs,
                                 faults="serve.dispatch:1.0:0:1")
        with server:
            bad = [server.submit(f"the cat {i} .", uuid=f"bad{i}",
                                 tier="spec") for i in range(2)]
            errors = []
            for f in bad:
                with pytest.raises(RuntimeError, match="injected"):
                    f.result(timeout=600)
                errors.append(f.error)
                # exactly-once: the future is terminal; a second resolve
                # would have raised inside the dispatcher (ServeFuture
                # contract) and the error is the typed injected cause
                assert f.done() and isinstance(f.error, RuntimeError)
            ok = server.submit("the dog ran .", uuid="ok",
                               tier="spec").result(timeout=600)
            assert ok.uuid == "ok" and ok.tier == "spec"
        assert _isolated_obs.counter("serve/errors_total").value == 2
        assert _isolated_obs.counter("serve/tier_spec_total").value == 1

    def test_continuous_mode_rejects_non_beam_tiers(self, _isolated_obs):
        hps = serve_hps(serve_mode="continuous", spec_draft="")

        class StubEngine:
            slots = 2

            def release(self, idx):
                pass

        server = ServingServer(hps, serve_vocab(), decoder=object(),
                               engine=StubEngine(), registry=_isolated_obs)
        with pytest.raises(ValueError, match="beam tier only"):
            server.submit("the cat .", tier="spec")


def test_decoder_rejects_spec_without_draft():
    hps = serve_hps(spec_draft="")
    vocab = serve_vocab()
    params = get_family(hps.model_family).init_params(
        hps, vocab.size(), jax.random.PRNGKey(0))
    import tempfile

    from textsummarization_on_flink_tpu.data.batching import (
        Batch,
        SummaryExample,
    )

    decoder = BeamSearchDecoder(hps, vocab, batcher=None, params=params,
                                decode_root=tempfile.mkdtemp(prefix="sd_"))
    assert not decoder.has_draft
    ex = SummaryExample.build("the cat .", [], vocab, hps, uuid="u")
    batch = Batch([ex] * hps.batch_size, hps, vocab)
    with pytest.raises(ValueError, match="draft model"):
        decoder.decode_batch(batch, tier="spec")
    with pytest.raises(ValueError, match="tier must be"):
        decoder.decode_batch(batch, tier="warp")
