"""The serial tick's host work, named where it happens: a real tiny
``SlotDecodeEngine`` under the ``ContinuousBatcher``, driven one round at
a time on this thread until every request resolves.

A harvest retires its finished slots with one device->host read: each
request's unpack program is ``decode/unpack/dispatch`` inside its own
``serve/harvest/unpack``, the one read of every slot's output is
``decode/unpack/fetch`` (once a harvest), and each request's
``decode/unpack/result`` and bookkeeping (``serve/harvest/resolve``)
follow, all three directly under ``serve/harvest``.  Each prefill is
``decode/prefill/batch`` and ``decode/prefill/dispatch`` under
``serve/prefill``.  Each is booked as often as named, in the span tree
under the phase named, and out of the coverage sum.
"""

import pytest

from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.vocab import Vocab
from textsummarization_on_flink_tpu.obs import profile as profile_lib
from textsummarization_on_flink_tpu.obs.registry import Registry

WORDS = ("the a cat dog sat ran mat home big small quick brown fox "
         "jumped over lazy it was day night").split()
ARTICLES = ["the quick brown fox jumped over the lazy dog .",
            "a big dog ran home .", "the cat sat .",
            "it was day and night and day ."]
N = 4

#: (child phase, the phase it opens inside) on the serving path
PARENT_OF = {
    "decode/unpack/dispatch": "serve/harvest/unpack",
    "decode/unpack/fetch": "serve/harvest",
    "decode/unpack/result": "serve/harvest",
    "serve/harvest/resolve": "serve/harvest",
    "serve/harvest/unpack": "serve/harvest",
    "decode/prefill/batch": "serve/prefill",
    "decode/prefill/dispatch": "serve/prefill",
    "serve/dispatch/wait_mask": "serve/dispatch",
}
TOP_LEVEL = ("serve/evict", "serve/prefill", "serve/pack", "serve/dispatch",
             "serve/harvest")


@pytest.fixture(scope="module")
def model():
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib

    vocab = Vocab(words=WORDS)
    hps = HParams(mode="decode", batch_size=4, hidden_dim=8, emb_dim=6,
                  vocab_size=vocab.size(), max_enc_steps=16,
                  max_dec_steps=6, beam_size=2, min_dec_steps=1,
                  max_oov_buckets=4, serve_max_queue=64,
                  serve_mode="continuous", serve_slots=2,
                  serve_refill_chunk=2, serve_buckets="8,16")
    params = trainer_lib.init_train_state(hps, vocab.size(), seed=0).params
    return hps, vocab, params


def _serve(hps, vocab, params, tmp_path, reg, n=N):
    """Submit `n` requests and tick the real server on this thread until
    all resolve; returns the results and the engine."""
    from textsummarization_on_flink_tpu.serve.server import ServingServer

    server = ServingServer(hps, vocab, params=params,
                           decode_root=str(tmp_path), registry=reg)
    futures = [server.submit(ARTICLES[i % len(ARTICLES)], uuid=f"t{i}")
               for i in range(n)]
    for _ in range(200):
        if all(f.done() for f in futures):
            break
        server.tick_once(poll=0.0)
    results = [f.result(timeout=0) for f in futures]
    engine = server._cont.engine
    server.stop()
    return results, engine


def test_each_child_phase_is_booked_once_under_its_parent(model, tmp_path):
    hps, vocab, params = model
    with obs.use_registry(Registry()) as reg:
        results, _ = _serve(hps, vocab, params, tmp_path, reg)
    assert [r.uuid for r in results] == [f"t{i}" for i in range(N)]
    prof = reg.profile
    counts = {k: v[0] for k, v in prof.phase_stats().items()}
    # once a request: its unpack program, its result and its rest
    for name in ("serve/harvest/unpack", "decode/unpack/dispatch",
                 "decode/unpack/result", "serve/harvest/resolve"):
        assert counts[name] == N, name
    # once a harvest that retires anything: the one read of its slots
    shared = reg.histogram("serve/harvest_slots")
    assert counts["decode/unpack/fetch"] == shared.count
    assert shared.sum == N
    # once a prefill: the batch and the dispatch
    assert counts["serve/prefill"] == N
    assert counts["decode/prefill/batch"] == counts["serve/prefill"]
    assert counts["decode/prefill/dispatch"] == counts["serve/prefill"]
    # one wait a chunk, inside the chunk's dispatch; no second bracket
    # over the same interval
    assert counts["serve/dispatch/wait_mask"] == counts["serve/dispatch"]
    assert "decode/slot_chunk" not in counts
    # each span's parent is the phase named above
    spans = obs.tracer_for(reg).finished()
    for s in spans:
        if s.name in PARENT_OF:
            assert s.parent == PARENT_OF[s.name], (s.name, s.parent)
    assert {s.name for s in spans} >= set(PARENT_OF)
    # the children are out of the coverage sum: coverage is the top-level
    # phases over the tick walls, as it was before they existed
    walls = profile_lib.profile_payload(reg)["walls"]
    assert [w["wall"] for w in walls] == ["serve/tick"]
    stats = prof.phase_stats()
    top = sum(stats[p][1] for p in TOP_LEVEL if p in stats)
    wall_s = sum(w[1] for w in prof._walls.values())
    assert prof.coverage() == pytest.approx(top / wall_s, rel=1e-9)
    assert reg.gauge("profile/phase_coverage_ratio").value == \
        pytest.approx(prof.coverage(), rel=1e-6)
    # the recent ring holds top-level phases only
    assert {r[1] for r in prof.recent_phases()} <= set(TOP_LEVEL)


def test_slots_finishing_together_share_one_read(model, tmp_path):
    """Two articles packed in one tick that cannot stop before
    max_dec_steps finish in the same chunk: their harvest makes one
    fetch for both, after both dispatches."""
    hps, vocab, params = model
    lockstep = hps.replace(min_dec_steps=hps.max_dec_steps)
    n = 2
    with obs.use_registry(Registry()) as reg:
        results, _ = _serve(lockstep, vocab, params, tmp_path, reg, n=n)
    assert [r.uuid for r in results] == ["t0", "t1"]
    counts = {k: v[0] for k, v in reg.profile.phase_stats().items()}
    assert counts["decode/unpack/fetch"] == 1
    for name in ("serve/harvest/unpack", "decode/unpack/dispatch",
                 "decode/unpack/result", "serve/harvest/resolve"):
        assert counts[name] == n, name
    shared = reg.histogram("serve/harvest_slots")
    assert (shared.count, shared.sum) == (1, n)
    # in the harvest: every dispatch, then the one read, then each
    # request's result and resolve (siblings finish in the order they
    # start)
    harvest = [s.name for s in obs.tracer_for(reg).finished()
               if s.parent == "serve/harvest"]
    assert harvest == (["serve/harvest/unpack"] * n
                       + ["decode/unpack/fetch"]
                       + ["decode/unpack/result"] * n
                       + ["serve/harvest/resolve"] * n)


def test_a_dark_engine_takes_the_shared_null_bracket(model, tmp_path):
    """obs=False: the engine's profiler is the shared null object, its
    brackets are the one null bracket, and a request still resolves
    through the phased unpack and prefill."""
    hps, vocab, params = model
    dark = hps.replace(obs=False)
    with obs.use_registry(Registry()) as reg:
        results, engine = _serve(dark, vocab, params, tmp_path, reg)
    assert [r.uuid for r in results] == [f"t{i}" for i in range(N)]
    assert engine._prof is profile_lib.NULL_PROFILER
    for name in ("decode/unpack/fetch", "decode/prefill/batch"):
        assert engine._prof.phase(name) is profile_lib.NULL_BRACKET
    # nothing reached the live default registry through the engine
    assert reg.get("profile/phase_seconds") is None or not any(
        c.labels_kv == (("phase", "decode/unpack/fetch"),)
        for c in reg.get("profile/phase_seconds").label_children())


def test_the_split_summaries_equal_the_unsplit_ones(model, tmp_path):
    """The fetch is a reordering of the same reads: the summaries of a
    phased unpack are the decoder's own batch search's, token for
    token."""
    hps, vocab, params = model
    with obs.use_registry(Registry()) as reg:
        results, _ = _serve(hps, vocab, params, tmp_path / "cont", reg)
        from textsummarization_on_flink_tpu.serve.server import (
            ServingServer,
        )
        mb = ServingServer(hps.replace(serve_mode="microbatch"), vocab,
                           params=params, decode_root=str(tmp_path / "mb"),
                           registry=reg)
        with mb:
            ref = [mb.submit(ARTICLES[i % len(ARTICLES)],
                             uuid=f"t{i}").result(timeout=300)
                   for i in range(N)]
    assert [r.summary for r in results] == [r.summary for r in ref]
    assert all(r.avg_log_prob == pytest.approx(q.avg_log_prob, abs=1e-5)
               for r, q in zip(results, ref))
