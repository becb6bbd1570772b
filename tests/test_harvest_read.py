"""The harvest's one device->host read: ``SlotDecodeEngine.unpack_many``
retires every finished slot with one ``jax.device_get`` over all their
outputs, gives each slot the result a per-slot ``unpack`` gives, and
frees every slot; under the ``ContinuousBatcher`` a retire that fails
fails its harvest only, each future once.
"""

import jax
import numpy as np
import pytest

from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.batching import SummaryExample
from textsummarization_on_flink_tpu.data.vocab import Vocab
from textsummarization_on_flink_tpu.obs.registry import Registry

WORDS = ("the a cat dog sat ran mat home big small quick brown fox "
         "jumped over lazy it was day night").split()
ARTICLES = ["the quick brown fox jumped over the lazy dog .",
            "a big dog ran home .", "the cat sat .",
            "it was day and night and day ."]
SLOTS = 3


@pytest.fixture(scope="module")
def model():
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib

    vocab = Vocab(words=WORDS)
    hps = HParams(mode="decode", batch_size=4, hidden_dim=8, emb_dim=6,
                  vocab_size=vocab.size(), max_enc_steps=16,
                  max_dec_steps=6, beam_size=2, min_dec_steps=1,
                  max_oov_buckets=4, serve_max_queue=64,
                  serve_mode="continuous", serve_slots=SLOTS,
                  serve_refill_chunk=2, serve_buckets="8,16")
    params = trainer_lib.init_train_state(hps, vocab.size(), seed=0).params
    return hps, vocab, params


def _engine(hps, vocab, params, tmp_path, n):
    """A tiny engine with `n` articles packed and decoded to the end,
    and the raw arguments of every result it builds."""
    from textsummarization_on_flink_tpu.decode.decoder import (
        BeamSearchDecoder,
    )

    dec = BeamSearchDecoder(hps, vocab, batcher=None, params=params,
                            decode_root=str(tmp_path))
    made = []
    make = dec._make_result

    def recording(tokens, length, attn_dists, p_gens, **kw):
        made.append((np.array(tokens), length, np.array(attn_dists),
                     np.array(p_gens), kw["avg_log_prob"]))
        return make(tokens, length, attn_dists, p_gens, **kw)

    dec._make_result = recording
    dec.params_fingerprint  # memoized here: its read is not a harvest's
    eng = dec.slot_engine(slots=SLOTS, chunk=2)
    examples = [SummaryExample.build(ARTICLES[i], [], vocab, hps,
                                     uuid=f"u{i}") for i in range(n)]
    for i, ex in enumerate(examples):
        eng.pack(i, ex)
    done = set()
    for _ in range(hps.max_dec_steps):
        done.update(eng.step())
    assert done == set(range(n))
    return eng, examples, made


@pytest.mark.parametrize("n", [1, 2, 3])
def test_one_read_gives_each_slot_its_own_unpack(model, tmp_path,
                                                 monkeypatch, n):
    hps, vocab, params = model
    batched, examples, made_batched = _engine(hps, vocab, params,
                                              tmp_path / "a", n)
    single, _, made_single = _engine(hps, vocab, params, tmp_path / "b", n)
    free = batched.free_pages()
    assert free == single.free_pages()

    reads = []
    device_get = jax.device_get

    def counting(x):
        reads.append(x)
        return device_get(x)

    def compiles():
        return batched.cache_sizes()["unpack_slot_jit"]

    monkeypatch.setattr(jax, "device_get", counting)
    pairs = [(i, ex) for i, ex in enumerate(examples)]
    before = compiles()
    got = batched.unpack_many(pairs)
    assert len(reads) == 1
    monkeypatch.setattr(jax, "device_get", device_get)
    warm = compiles()
    want = [single.unpack(i, ex) for i, ex in pairs]
    # one compile of the unpack program, whatever the number of slots
    assert warm - before <= 1 and compiles() == warm

    assert [r.uuid for r in got] == [r.uuid for r in want] == \
        [ex.uuid for ex in examples]
    assert [r.decoded_words for r in got] == [r.decoded_words for r in want]
    # field for field, the raw output each result was built from
    assert len(made_batched) == len(made_single) == n
    for (t, L, a, p, lp), (t2, L2, a2, p2, lp2) in zip(made_batched,
                                                       made_single):
        np.testing.assert_array_equal(t, t2)
        assert L == L2 and isinstance(L, int)
        np.testing.assert_array_equal(a, a2)
        np.testing.assert_array_equal(p, p2)
        assert lp == lp2 and isinstance(lp, float)
    # every slot freed: its pages back in the arena, its bit cleared
    for eng in (batched, single):
        assert eng.active_count() == 0
        assert not eng._active.any()
        assert eng.free_pages() == eng.arena_stats()["capacity"] > free


def test_a_slot_that_is_not_resident_changes_nothing(model, tmp_path):
    hps, vocab, params = model
    eng, examples, _ = _engine(hps, vocab, params, tmp_path, 2)
    free = eng.free_pages()
    with pytest.raises(AssertionError, match="slot 2 is not resident"):
        eng.unpack_many([(0, examples[0]), (2, examples[1])])
    assert eng.active_count() == 2 and eng.free_pages() == free


@pytest.mark.parametrize("where", ["dispatch", "read"])
def test_a_failed_retire_fails_its_harvest_only(model, tmp_path,
                                                monkeypatch, where):
    """Two requests finish together while a third, packed a tick later,
    is still decoding; the harvest's retire raises in the second slot's
    unpack dispatch or in the one read.  The two futures are rejected
    once each with the cause, their slots and pages are freed, and the
    third request, and one submitted after, resolve normally."""
    from textsummarization_on_flink_tpu.decode.decoder import (
        SlotDecodeEngine,
    )
    from textsummarization_on_flink_tpu.serve.server import ServingServer

    hps, vocab, params = model
    lockstep = hps.replace(min_dec_steps=hps.max_dec_steps)
    boom = RuntimeError(f"injected {where} failure")
    state = {"armed": True, "unpacks": 0}
    jitted = SlotDecodeEngine._jitted

    def failing_jitted(self, site, *a, **kw):
        if site == "decode/unpack_slot_jit" and state["armed"]:
            state["unpacks"] += 1
            if where == "dispatch" and state["unpacks"] == 2:
                state["armed"] = False
                raise boom
        return jitted(self, site, *a, **kw)

    device_get = jax.device_get

    def failing_get(x):
        if where == "read" and state["armed"] and state["unpacks"] == 2:
            state["armed"] = False
            raise boom
        return device_get(x)

    monkeypatch.setattr(SlotDecodeEngine, "_jitted", failing_jitted)
    monkeypatch.setattr(jax, "device_get", failing_get)
    with obs.use_registry(Registry()) as reg:
        server = ServingServer(lockstep, vocab, params=params,
                               decode_root=str(tmp_path), registry=reg)
        engine = server._cont.engine
        capacity = engine.arena_stats()["capacity"]
        first = [server.submit(ARTICLES[i], uuid=f"t{i}") for i in range(2)]
        server.tick_once(poll=0.0)
        assert engine.active_count() == 2
        late = server.submit(ARTICLES[2], uuid="late")
        for _ in range(200):
            if all(f.done() for f in first):
                break
            server.tick_once(poll=0.0)
        assert not state["armed"]
        for f in first:
            assert f.done()
            with pytest.raises(RuntimeError, match=f"injected {where}"):
                f.result(timeout=0)
        assert reg.counter("serve/errors_total").value == 2
        assert not late.done()
        assert engine.active_count() == 1
        after = server.submit(ARTICLES[3], uuid="after")
        for _ in range(200):
            if late.done() and after.done():
                break
            server.tick_once(poll=0.0)
        assert late.result(timeout=0).uuid == "late"
        assert after.result(timeout=0).uuid == "after"
        assert reg.counter("serve/completed_total").value == 2
        assert reg.counter("serve/errors_total").value == 2
        assert engine.active_count() == 0
        assert engine.free_pages() == capacity
        server.stop()
