"""The serving front door (ISSUE 14; SERVING.md "Front door"):
content-hash normalization, the bounded LRU summary cache, in-flight
coalescing, per-tenant token-bucket admission, the params-fingerprint
surface, and the cache-fault chaos contract.

The virtual-time SLO scenarios (zipf decode ratio, tenant isolation,
fleet composition with replica kill) live in tests/test_serve_slo.py;
this file pins the mechanisms one at a time, plus the two real-model
acceptance pins: a cache hit is byte-identical to a fresh decode, and
a checkpoint hot-swap changes the fingerprint and thereby MISSES.
"""

import threading

import numpy as np
import pytest

from textsummarization_on_flink_tpu.serve.batcher import NoArena
from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.checkpoint.checkpointer import (
    Checkpointer,
)
from textsummarization_on_flink_tpu.config import (
    HParams,
    parse_fair_weights,
    resolve_tenant_burst,
)
from textsummarization_on_flink_tpu.data.vocab import Vocab
from textsummarization_on_flink_tpu.decode.decoder import DecodedResult
from textsummarization_on_flink_tpu.obs import Registry
from textsummarization_on_flink_tpu.obs.export import MemorySink
from textsummarization_on_flink_tpu.pipeline.io import Message
from textsummarization_on_flink_tpu.serve import (
    ServeOverloadError,
    TenantThrottledError,
)
from textsummarization_on_flink_tpu.serve.frontdoor import (
    FrontDoor,
    SummaryCache,
    article_key,
)
from textsummarization_on_flink_tpu.serve.server import ServingServer
from textsummarization_on_flink_tpu.train import trainer as trainer_lib

WORDS = ("the a cat dog sat ran mat home big small quick brown fox "
         "jumped over lazy it was day night").split()


@pytest.fixture(autouse=True)
def _isolated_obs():
    with obs.use_registry(Registry()) as reg:
        yield reg


def make_vocab():
    return Vocab(words=WORDS)


def tiny_hps(**kw):
    base = dict(mode="decode", batch_size=4, hidden_dim=8, emb_dim=6,
                vocab_size=24, max_enc_steps=16, max_dec_steps=6,
                beam_size=2, min_dec_steps=1, max_oov_buckets=4,
                serve_max_wait_ms=20.0, serve_max_queue=64)
    base.update(kw)
    return HParams(**base)


def make_result(uuid="u0", article="the cat sat .", words=("ok", "."),
                fingerprint=""):
    return DecodedResult(uuid=uuid, article=article,
                         decoded_words=list(words), reference="",
                         abstract_sents=[],
                         params_fingerprint=fingerprint)


class StubDecoder:
    """decode_batch stub with a settable fingerprint — the hot-swap
    invalidation mechanism without a checkpoint dir."""

    def __init__(self, fingerprint="fpA", fail=False):
        self.params_fingerprint = fingerprint
        self.fail = fail
        self.dispatches = 0

    def should_degrade(self, deadline):
        return False

    def decode_batch(self, batch, deadline=None, tier=None):
        self.dispatches += 1
        if self.fail:
            raise RuntimeError("injected decode failure")
        # content-deterministic output, like the real decoder: two
        # decodes of the same article produce identical words
        return [DecodedResult(
                    uuid=batch.uuids[b], article=batch.original_articles[b],
                    decoded_words=["ok"]
                    + batch.original_articles[b].split()[:2],
                    reference=batch.references[b], abstract_sents=[],
                    tier=tier or "beam",
                    params_fingerprint=self.params_fingerprint)
                for b in range(len(batch.uuids)) if batch.real_mask[b]]

    def maybe_reload_checkpoint(self, last):
        return last


# -- content-hash normalization (satellite 1) ------------------------------

class TestArticleKey:
    def test_socket_and_direct_paths_hash_identically(self):
        """The ONE canonical helper: an article round-tripped through
        the SocketSource line codec (Message JSON) hashes exactly like
        the same article submitted directly."""
        article = "the quick brown fox jumped over the lazy dog ."
        wire = Message(uuid="u1", article=article).to_json()
        decoded = Message.from_json(wire).article
        assert article_key(decoded, 16) == article_key(article, 16)

    def test_truncation_happens_before_hashing(self):
        """Two articles identical in the visible max_enc_steps window
        coalesce; a difference INSIDE the window does not."""
        window = "w1 w2 w3 w4"
        assert article_key(window + " tail one", 4) == \
            article_key(window + " a completely different tail", 4)
        assert article_key("w1 w2 XX w4 tail", 4) != \
            article_key(window + " tail", 4)

    def test_whitespace_is_normalized_bytes_level(self):
        assert article_key("a  b\tc\n", 8) == article_key("a b c", 8)

    def test_distinct_content_distinct_keys(self):
        assert article_key("the cat sat .", 16) != \
            article_key("the dog sat .", 16)


# -- the summary cache ------------------------------------------------------

class TestSummaryCache:
    def test_lru_eviction_at_entry_bound(self, _isolated_obs):
        cache = SummaryCache(2, registry=_isolated_obs)
        cache.put(("k1", "beam", ""), make_result("u1"))
        cache.put(("k2", "beam", ""), make_result("u2"))
        assert cache.get(("k1", "beam", "")) is not None  # touch: k1 MRU
        cache.put(("k3", "beam", ""), make_result("u3"))  # evicts k2
        assert cache.get(("k2", "beam", "")) is None
        assert cache.get(("k1", "beam", "")) is not None
        assert cache.get(("k3", "beam", "")) is not None
        assert _isolated_obs.counter(
            "serve/cache_evictions_total").value == 1
        assert _isolated_obs.gauge("serve/cache_entries").value == 2

    def test_byte_bound_evicts_lru_first(self, _isolated_obs):
        big = ["w" * 100] * 10  # ~1 KB payload
        cache = SummaryCache(64, max_bytes=2500, registry=_isolated_obs)
        for i in range(4):
            cache.put((f"k{i}", "beam", ""), make_result(words=big))
        assert len(cache) < 4, "the byte bound never evicted"
        assert cache.nbytes <= 2500
        assert _isolated_obs.counter(
            "serve/cache_evictions_total").value >= 1

    def test_fingerprint_is_part_of_the_key(self, _isolated_obs):
        cache = SummaryCache(8, registry=_isolated_obs)
        cache.put(("k", "beam", "fpA"), make_result())
        assert cache.get(("k", "beam", "fpB")) is None
        assert cache.get(("k", "greedy", "fpA")) is None
        assert cache.get(("k", "beam", "fpA")) is not None

    def test_caller_mutation_cannot_poison_the_cache(self, _isolated_obs):
        """The cache holds its own payload copy: a consumer editing a
        returned result's decoded_words in place must not change what
        the next hit serves (the byte-identical contract)."""
        hps = tiny_hps(serve_cache_entries=8)
        dec = StubDecoder()
        server = ServingServer(hps, make_vocab(), decoder=dec,
                               registry=_isolated_obs)
        with server:
            r1 = server.submit("the cat sat .",
                               uuid="m1").result(timeout=10)
            clean = list(r1.decoded_words)
            r1.decoded_words[0] = "MUTATED"  # a rude caller
            r2 = server.submit("the cat sat .",
                               uuid="m2").result(timeout=10)
            assert r2.decoded_words == clean
            r2.decoded_words.append("ALSO-MUTATED")
            r3 = server.submit("the cat sat .",
                               uuid="m3").result(timeout=10)
            assert r3.decoded_words == clean
        assert dec.dispatches == 1  # both repeats were real hits

    def test_degraded_results_never_cache(self, _isolated_obs):
        """A beam request that fell to greedy under deadline pressure
        is NOT byte-identical to a fresh beam decode — filing it under
        the beam key would poison every later hit, so degraded results
        skip the fill (followers still share them; that is the
        coalescing contract, not the cache's)."""
        hps = tiny_hps(serve_cache_entries=8)
        door = FrontDoor(hps, registry=_isolated_obs)
        kind, flight = door.open("the cat sat .", "beam", "L", "")
        assert kind == "leader"
        from textsummarization_on_flink_tpu.serve.queue import ServeFuture

        fut = ServeFuture("L", registry=_isolated_obs)
        door.commit(flight, fut)
        res = make_result("L")
        res.degraded = True
        fut._resolve(res)
        assert len(door.cache) == 0
        assert _isolated_obs.gauge("serve/cache_entries").value == 0

    def test_hit_observes_entry_age(self, _isolated_obs):
        t = [0.0]
        cache = SummaryCache(8, registry=_isolated_obs,
                             clock=lambda: t[0])
        cache.put(("k", "beam", ""), make_result())
        t[0] = 2.5
        cache.get(("k", "beam", ""))
        h = _isolated_obs.histogram("serve/cache_entry_age_seconds")
        assert h.count == 1 and abs(h.mean - 2.5) < 1e-6


# -- coalescing through the real server ------------------------------------

class TestCoalescing:
    def test_followers_resolve_once_from_one_decode(self, _isolated_obs):
        sink = MemorySink()
        _isolated_obs.event_sink = sink
        hps = tiny_hps(serve_coalesce=True)
        dec = StubDecoder()
        server = ServingServer(hps, make_vocab(), decoder=dec,
                               registry=_isolated_obs)
        futs = [server.submit("the cat sat .", uuid=f"c{i}")
                for i in range(5)]
        futs.append(server.submit("the dog ran .", uuid="d0"))
        server.start()
        results = [f.result(timeout=10) for f in futs]
        server.stop()
        # exactly-once, own identity columns, identical decoded words
        assert [r.uuid for r in results] == \
            ["c0", "c1", "c2", "c3", "c4", "d0"]
        assert len({" ".join(r.decoded_words) for r in results[:5]}) == 1
        assert results[5].decoded_words != results[0].decoded_words
        assert _isolated_obs.counter("serve/coalesced_total").value == 4
        # one decode for the coalesced five: completed counts LEADERS
        assert _isolated_obs.counter("serve/completed_total").value == 2
        events = [r for r in sink.records() if r.get("kind") == "request"]
        co = [e for e in events if e.get("event") == "coalesced"]
        assert len(co) == 4
        assert all(e["attrs"]["leader"] == "c0" for e in co)
        # a follower's timeline closes: coalesced -> resolve, per uuid
        for e in co:
            uid = e["uuid"]
            assert any(r.get("event") == "resolve" and r["uuid"] == uid
                       for r in events)

    def test_leader_failure_fails_followers_typed(self, _isolated_obs):
        hps = tiny_hps(serve_coalesce=True)
        dec = StubDecoder(fail=True)
        server = ServingServer(hps, make_vocab(), decoder=dec,
                               registry=_isolated_obs)
        futs = [server.submit("the cat sat .", uuid=f"c{i}")
                for i in range(3)]
        server.start()
        for f in futs:
            with pytest.raises(RuntimeError, match="injected decode"):
                f.result(timeout=10)
        server.stop()
        # the flight is retired: a NEW submit leads a fresh computation
        dec.fail = False
        server2 = ServingServer(hps, make_vocab(), decoder=dec,
                                registry=_isolated_obs)
        with server2:
            assert server2.submit("the cat sat .",
                                  uuid="n0").result(timeout=10).uuid == "n0"

    def test_abort_rejects_attached_followers(self, _isolated_obs):
        """A leader bounced at admission fails its already-attached
        followers with the same typed cause (never a hang)."""
        door = FrontDoor(tiny_hps(serve_coalesce=True),
                         registry=_isolated_obs)
        kind, flight = door.open("the cat sat .", "beam", "L", "")
        assert kind == "leader"
        kind2, follower = door.open("the cat sat .", "beam", "F", "")
        assert kind2 == "follower"
        door.abort(flight, ServeOverloadError("queue full"))
        with pytest.raises(ServeOverloadError, match="queue full"):
            follower.result(timeout=1)
        assert door.inflight() == 0

    def test_synchronous_submit_error_never_leaks_the_flight(
            self, _isolated_obs):
        """A leader whose submit raises SYNCHRONOUSLY (here: a tier the
        continuous server refuses) must retire its flight — a later
        duplicate leads a FRESH computation instead of attaching to a
        leader that never existed (which would hang forever)."""
        from textsummarization_on_flink_tpu.serve.fleet import FleetRouter

        hps = tiny_hps(serve_coalesce=True, serve_mode="continuous",
                       serve_slots=2, serve_refill_chunk=2)

        class _Eng(NoArena):
            slots, chunk = 2, 2

            def pack(self, idx, ex):
                pass

            def step(self):
                return []

            def unpack(self, idx, ex):
                raise AssertionError("never reached")

            def release(self, idx):
                pass

        class _Null:
            def maybe_reload_checkpoint(self, last):
                return last

        server = ServingServer(hps, make_vocab(), decoder=_Null(),
                               engine=_Eng(), registry=_isolated_obs)
        router = FleetRouter([server], hps, registry=_isolated_obs)
        # greedy on a continuous fleet: the REPLICA raises ValueError
        # inside router.submit, after the router registered the flight
        with pytest.raises(ValueError, match="beam tier only"):
            router.submit("the cat sat .", uuid="bad0", tier="greedy")
        assert router._door.inflight() == 0, (
            "the failed leader's flight leaked — later duplicates "
            "would hang")
        # and the single-server path: a full queue bounces the leader
        hps2 = tiny_hps(serve_coalesce=True, serve_max_queue=1)
        dec = StubDecoder()
        s2 = ServingServer(hps2, make_vocab(), decoder=dec,
                           registry=_isolated_obs)
        s2.submit("the dog ran .", uuid="fill")  # occupies the queue
        with pytest.raises(ServeOverloadError):
            s2.submit("the cat sat .", uuid="lead0")
        # only the FILL request's (legitimate) flight remains; the
        # bounced leader's was retired
        assert s2._door.inflight() == 1

    def test_coalescing_respects_the_tier_axis(self, _isolated_obs):
        """(content_hash, tier) is the flight key: the same article at
        two tiers never shares a decode (different compiled programs,
        different quality contracts)."""
        hps = tiny_hps(serve_coalesce=True)
        dec = StubDecoder()
        server = ServingServer(hps, make_vocab(), decoder=dec,
                               registry=_isolated_obs)
        f1 = server.submit("the cat sat .", uuid="b0", tier="beam")
        f2 = server.submit("the cat sat .", uuid="g0", tier="greedy")
        server.start()
        r1, r2 = f1.result(timeout=10), f2.result(timeout=10)
        server.stop()
        assert (r1.tier, r2.tier) == ("beam", "greedy")
        assert _isolated_obs.counter("serve/coalesced_total").value == 0


# -- tenant admission -------------------------------------------------------

class TestTenantAdmission:
    def test_bucket_sheds_typed_and_refills_on_the_clock(
            self, _isolated_obs):
        t = [0.0]
        hps = tiny_hps(serve_tenant_rate=2.0, serve_tenant_burst=2)
        door = FrontDoor(hps, registry=_isolated_obs, clock=lambda: t[0])
        door.admit_tenant("acme", "u0")
        door.admit_tenant("acme", "u1")  # burst spent
        with pytest.raises(TenantThrottledError):
            door.admit_tenant("acme", "u2")
        # another tenant's bucket is untouched
        door.admit_tenant("other", "o0")
        assert _isolated_obs.counter("serve/tenant_shed_total").value == 1
        t[0] = 0.5  # 0.5 s at 2/s -> one token back
        door.admit_tenant("acme", "u3")
        with pytest.raises(TenantThrottledError):
            door.admit_tenant("acme", "u4")

    def test_throttled_is_an_overload_subclass(self):
        assert issubclass(TenantThrottledError, ServeOverloadError)

    def test_rate_zero_is_todays_behavior(self, _isolated_obs):
        door = FrontDoor(tiny_hps(), registry=_isolated_obs)
        assert not door.armed
        for i in range(100):
            door.admit_tenant("anyone", f"u{i}")  # never sheds

    def test_burst_resolver_and_weights_parser_validate(self):
        assert resolve_tenant_burst(
            HParams(serve_tenant_rate=0.5)) == 1
        assert parse_fair_weights("a:2, b:0.5") == {"a": 2.0, "b": 0.5}
        with pytest.raises(ValueError, match="tenant:weight"):
            parse_fair_weights("nocolon")
        with pytest.raises(ValueError, match="> 0"):
            parse_fair_weights("a:0")
        with pytest.raises(ValueError, match="names no tenant"):
            parse_fair_weights(":3")
        with pytest.raises(ValueError, match="not a number"):
            HParams(serve_fair_weights="a:x").validate()


# -- cache-fault chaos (satellite 3) ----------------------------------------

class TestCacheFaultChaos:
    def test_cache_fault_degrades_to_miss_and_decode(self, _isolated_obs):
        """With serve.cache_fault armed at p=1, every lookup degrades
        to a miss and every insert drops: requests still decode and
        resolve correctly (never a wrong summary, never a hang), and
        the degradation is counted."""
        hps = tiny_hps(serve_cache_entries=8,
                       faults="serve.cache_fault:1.0:0")
        dec = StubDecoder()
        server = ServingServer(hps, make_vocab(), decoder=dec,
                               registry=_isolated_obs)
        with server:
            r1 = server.submit("the cat sat .",
                               uuid="x1").result(timeout=10)
            r2 = server.submit("the cat sat .",
                               uuid="x2").result(timeout=10)
        assert r1.decoded_words == r2.decoded_words
        assert dec.dispatches == 2, "both must decode (cache dark)"
        assert _isolated_obs.counter("serve/cache_hits_total").value == 0
        assert _isolated_obs.counter(
            "serve/cache_errors_total").value >= 2

    def test_stopped_server_refuses_cached_articles_too(
            self, _isolated_obs):
        """The shutdown contract must not depend on what happens to be
        cached: after stop(), a CACHED article's submit raises the same
        typed ServeClosedError an uncached one does."""
        from textsummarization_on_flink_tpu.serve import ServeClosedError

        hps = tiny_hps(serve_cache_entries=8)
        dec = StubDecoder()
        server = ServingServer(hps, make_vocab(), decoder=dec,
                               registry=_isolated_obs)
        with server:
            server.submit("the cat sat .", uuid="u1").result(timeout=10)
        with pytest.raises(ServeClosedError):
            server.submit("the cat sat .", uuid="u2")  # cached article
        with pytest.raises(ServeClosedError):
            server.submit("the dog ran .", uuid="u3")  # uncached

    def test_healthy_cache_same_workload_hits(self, _isolated_obs):
        """The control run: same workload, no fault — the second
        submit is a hit and must be byte-identical to the first."""
        hps = tiny_hps(serve_cache_entries=8)
        dec = StubDecoder()
        server = ServingServer(hps, make_vocab(), decoder=dec,
                               registry=_isolated_obs)
        with server:
            r1 = server.submit("the cat sat .",
                               uuid="x1").result(timeout=10)
            r2 = server.submit("the cat sat .",
                               uuid="x2").result(timeout=10)
        assert dec.dispatches == 1
        assert r2.as_row()[2] == r1.as_row()[2]  # summary byte-identical
        assert _isolated_obs.counter("serve/cache_hits_total").value == 1


# -- params fingerprint + hot-swap invalidation (satellite 2) ---------------

class TestFingerprintHotSwap:
    def test_hot_swap_changes_fingerprint_and_misses(
            self, _isolated_obs, tmp_path):
        """The acceptance pin on a REAL tiny model: a cache hit is
        byte-identical to its original decode; after a checkpoint
        hot-swap the same article MISSES (new fingerprint) and
        re-decodes under the new params."""
        vocab = make_vocab()
        hps = tiny_hps(vocab_size=vocab.size(), serve_cache_entries=8)
        train_dir = str(tmp_path / "train")
        ck = Checkpointer(train_dir, hps=hps)
        state_a = trainer_lib.init_train_state(hps, vocab.size(), seed=0)
        ck.save(state_a)
        server = ServingServer(
            hps, vocab, train_dir=train_dir,
            decode_root=str(tmp_path / "dec"), registry=_isolated_obs)
        with server:
            fp_a = server.params_fingerprint
            assert fp_a and len(fp_a) == 16
            # /healthz carries the same surface (ISSUE 14 satellite)
            assert _isolated_obs.health_info["params_fingerprint"] == fp_a
            r1 = server.submit("the cat sat on the mat .",
                               uuid="u1").result(timeout=600)
            assert r1.params_fingerprint == fp_a
            done1 = _isolated_obs.counter("serve/completed_total").value
            r2 = server.submit("the cat sat on the mat .",
                               uuid="u2").result(timeout=600)
            # byte-identical hit, no second decode
            assert r2.as_row()[1:] == ("the cat sat on the mat .",
                                       r1.as_row()[2], "")
            assert _isolated_obs.counter(
                "serve/completed_total").value == done1
            assert _isolated_obs.counter(
                "serve/cache_hits_total").value == 1
            # a NEW checkpoint with different params, force-swapped
            state_b = trainer_lib.init_train_state(hps, vocab.size(),
                                                   seed=7)
            state_b = state_b._replace(step=np.asarray(1, np.int32))
            ck.save(state_b)
            assert server.hot_swap()
            fp_b = server.params_fingerprint
            assert fp_b != fp_a, "hot-swap must change the fingerprint"
            assert _isolated_obs.health_info["params_fingerprint"] == fp_b
            r3 = server.submit("the cat sat on the mat .",
                               uuid="u3").result(timeout=600)
            # MISSED and re-decoded under the new snapshot
            assert _isolated_obs.counter(
                "serve/completed_total").value == done1 + 1
            assert r3.params_fingerprint == fp_b

    def test_fingerprint_cached_per_params_object(self, _isolated_obs,
                                                  tmp_path):
        """The sha runs once per swap, not per request: repeated reads
        return the identical object-cached string."""
        from textsummarization_on_flink_tpu.decode.decoder import (
            BeamSearchDecoder,
        )

        vocab = make_vocab()
        hps = tiny_hps(vocab_size=vocab.size())
        params = trainer_lib.init_train_state(hps, vocab.size(),
                                              seed=0).params
        dec = BeamSearchDecoder(hps, vocab, batcher=None, params=params,
                                decode_root=str(tmp_path))
        fp1 = dec.params_fingerprint
        assert dec.params_fingerprint is fp1  # memoized, not recomputed
        # the slot engine reports the SAME surface
        assert dec.slot_engine(slots=2,
                               chunk=2).params_fingerprint == fp1
