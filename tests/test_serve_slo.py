"""The committed serving-SLO regression gate (ISSUE 6; SERVING.md
"Continuous batching").

A count, not a speed: it needs no chip and says nothing about one.
The continuous-batching claim is about
SCHEDULING — kill the micro-batch dispatch-window barrier so one long
article stops holding its neighbors hostage — so the gate runs the REAL
serving stack (ServingServer dispatch threads, RequestQueue,
MicroBatcher, ContinuousBatcher) over a deterministic VIRTUAL-TIME cost
model instead of a device:

  * a decode dispatch of d steps costs d * step_cost virtual ms, and a
    batch costs max(d_i) — exactly the device's straggler shape;
  * a continuous chunk costs chunk * step_cost regardless of occupancy;
  * every request is enqueued BEFORE the dispatch thread starts, so
    group/slot assignment is pure FIFO and the whole run is replayable.

No sleeps, no wall-clock assertions — CI load cannot flake the gate,
and the numbers in SERVE_SLO.json are exact scheduling facts with
modest headroom (see its _comment for the re-baselining rule).  The
wall-clock story at real-model scale lives in ``bench.py --serve``; the
kernel-level "no per-request recompiles" claim is pinned by
tests/test_serve.py (bounded jit cache) and tests/test_beam_search.py
(slot parity).

Enforced here, in tier-1:
  * continuous-mode p99 enqueue->resolved latency (virtual ms) stays
    under its committed ceiling on the bimodal load;
  * continuous-mode mean slot occupancy stays above its floor;
  * continuous BEATS the micro-batch baseline at equal request load on
    both p99 latency and occupancy/utilization by the committed margins;
  * exactly-once resolution holds for every request in both modes.
"""

import json
import os
import random

import pytest

from textsummarization_on_flink_tpu.serve.batcher import NoArena
from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.vocab import Vocab
from textsummarization_on_flink_tpu.decode.decoder import DecodedResult
from textsummarization_on_flink_tpu.obs import Registry
from textsummarization_on_flink_tpu.resilience.errors import (
    ArenaExhaustedError,
)
from textsummarization_on_flink_tpu.serve.server import ServingServer

SLO_PATH = os.path.join(os.path.dirname(__file__), "..", "SERVE_SLO.json")

WORDS = ["w"]


@pytest.fixture(scope="module")
def slo():
    with open(SLO_PATH) as f:
        return json.load(f)


def _steps_for(example, wl) -> int:
    """The virtual decode cost of one request, derived from its article
    length — the bimodal mix: short articles decode in few steps, long
    ones run to the horizon (the straggler)."""
    short = example.enc_len <= wl["short_words"]
    return wl["short_steps"] if short else wl["long_steps"]


class _NullDecoder:
    """Continuous mode drives the engine, not the decoder; only the
    between-chunk hot-swap hook is ever called."""

    def maybe_reload_checkpoint(self, last):
        return last


class SimEngine(NoArena):
    """SlotDecodeEngine protocol over virtual time: each step() advances
    the shared clock by chunk * step_cost and every active slot by
    `chunk` steps.  Records each request's RESOLVE time on the virtual
    clock at unpack — enqueue is t=0 by construction (all requests are
    queued before the dispatch thread starts)."""

    def __init__(self, wl):
        self.slots = wl["slots"]
        self.chunk = wl["chunk"]
        self._wl = wl
        self._cost = wl["step_cost_ms"]
        self._remaining = [0] * self.slots
        self._active = [False] * self.slots
        self.vtime = 0.0
        self.vresolve = {}

    def pack(self, idx, example):
        assert not self._active[idx]
        self._active[idx] = True
        self._remaining[idx] = _steps_for(example, self._wl)

    def step(self):
        self.vtime += self.chunk * self._cost
        fin = []
        for i in range(self.slots):
            if self._active[i]:
                self._remaining[i] -= self.chunk
                if self._remaining[i] <= 0:
                    fin.append(i)
        return fin

    def unpack(self, idx, example):
        assert self._active[idx]
        self._active[idx] = False
        self.vresolve[example.uuid] = self.vtime
        return DecodedResult(
            uuid=example.uuid, article=example.original_article,
            decoded_words=["ok", "."], reference=example.reference,
            abstract_sents=[])

    def release(self, idx):
        self._active[idx] = False


class _Prefilled:
    """The DisaggSimEngine's prefill handle (the PrefilledArticle
    analogue): steps remaining + the bucket the encoder pass ran at +
    the article's true length for the length-masked chunk cost."""

    def __init__(self, example, steps, bucket, words):
        self.example = example
        self.steps = steps
        self.bucket = bucket
        self.words = words


class DisaggSimEngine(SimEngine):
    """The DISAGGREGATED cost model (ISSUE 11) over the same virtual
    clock, driven through the REAL ContinuousBatcher prefill queue:

      * ``prefill(example)`` — the bucketed encoder stage — costs
        bucket(words) * prefill_ms_per_word (encoder work scales with
        the article's bucket, the BYTE_BUDGET.json decode.prefill
        claim);
      * each chunk costs chunk * step_cost * max(floor,
        longest_active_words / long_words) — the length-masked decode
        (per-chunk work follows the longest ACTIVE resident's true
        length, the decode.length_axis claim; `floor` models the
        length-independent share of the step: vocab projection, beam
        bookkeeping).
    """

    def __init__(self, wl):
        super().__init__(wl)
        self._words = [0] * self.slots

    def _bucket(self, words):
        for b in self._wl["buckets"]:
            if words <= b:
                return b
        return self._wl["buckets"][-1]

    def prefill(self, example):
        bucket = self._bucket(example.enc_len)
        self.vtime += bucket * self._wl["prefill_ms_per_word"]
        return _Prefilled(example, _steps_for(example, self._wl), bucket,
                          example.enc_len)

    def pack(self, idx, pre):
        assert not self._active[idx]
        self._active[idx] = True
        self._remaining[idx] = pre.steps
        self._words[idx] = pre.words

    def step(self):
        longest = max((self._words[i] for i in range(self.slots)
                       if self._active[i]), default=0)
        frac = max(self._wl["decode_len_floor"],
                   longest / self._wl["long_words"])
        self.vtime += self.chunk * self._cost * frac
        fin = []
        for i in range(self.slots):
            if self._active[i]:
                self._remaining[i] -= self.chunk
                if self._remaining[i] <= 0:
                    fin.append(i)
        return fin


class UniformSimEngine(SimEngine):
    """The PRE-CHANGE one-resident-shape cost model: every admission
    pays the FULL-width encoder (pack cost = long_words *
    prefill_ms_per_word regardless of article length — what
    pack_slot_jit did before the prefill stage existed) and every chunk
    costs full width (no length mask).  No ``prefill`` surface, so the
    ContinuousBatcher runs its legacy direct-pack path — the baseline
    the disaggregated section's ratios are committed against."""

    def pack(self, idx, example):
        self.vtime += self._wl["long_words"] * \
            self._wl["prefill_ms_per_word"]
        super().pack(idx, example)


class SimDecoder:
    """decode_batch over the same virtual cost model: one dispatch costs
    max(d_i) * step_cost — every member of the batch, short or long,
    resolves when the SLOWEST one does (the barrier this PR removes).
    Also records per-batch utilization sum(d_i)/(B * max(d_i)): the
    fraction of slot-steps doing useful work, the honest micro-batch
    analogue of slot occupancy (batch fill alone hides the straggler
    waste)."""

    def __init__(self, wl):
        self._wl = wl
        self._cost = wl["step_cost_ms"]
        self.vtime = 0.0
        self.vresolve = {}
        self.utilizations = []

    def decode_batch(self, batch, deadline=None):
        steps = [
            _steps_for_len(int(batch.enc_lens[b]), self._wl)
            for b in range(len(batch.uuids)) if batch.real_mask[b]]
        self.vtime += max(steps) * self._cost
        self.utilizations.append(
            sum(steps) / (len(batch.real_mask) * max(steps)))
        out = []
        for b in range(len(batch.uuids)):
            if not batch.real_mask[b]:
                continue
            self.vresolve[batch.uuids[b]] = self.vtime
            out.append(DecodedResult(
                uuid=batch.uuids[b], article=batch.original_articles[b],
                decoded_words=["ok", "."], reference=batch.references[b],
                abstract_sents=[]))
        return out

    def maybe_reload_checkpoint(self, last):
        return last


def _steps_for_len(enc_len: int, wl) -> int:
    return wl["short_steps"] if enc_len <= wl["short_words"] \
        else wl["long_steps"]


def _articles(wl):
    """The seeded bimodal request mix: `requests` articles, every
    `long_every`-th one long, shuffled with the committed seed so the
    arrival order interleaves modes (a straggler lands in most
    micro-batches, like production traffic)."""
    arts = []
    for i in range(wl["requests"]):
        n = wl["long_words"] if i % wl["long_every"] == 0 \
            else wl["short_words"]
        arts.append(" ".join(["w"] * n))
    random.Random(wl["seed"]).shuffle(arts)
    return arts


def _run_mode(wl, mode):
    """Drive the full load through a real ServingServer in `mode`;
    returns (per-uuid virtual resolve times, registry, sim)."""
    vocab = Vocab(words=WORDS)
    hps = HParams(
        mode="decode", batch_size=wl["batch_size"], vocab_size=vocab.size(),
        max_enc_steps=wl["long_words"], max_dec_steps=wl["long_steps"],
        beam_size=2, min_dec_steps=1, max_oov_buckets=4,
        serve_max_queue=max(4 * wl["requests"], 64),
        serve_max_wait_ms=5.0, serve_mode=mode, serve_slots=wl["slots"],
        serve_refill_chunk=wl["chunk"])
    with obs.use_registry(Registry()) as reg:
        if mode == "continuous":
            sim = SimEngine(wl)
            server = ServingServer(hps, vocab, decoder=_NullDecoder(),
                                   engine=sim, registry=reg)
        else:
            sim = SimDecoder(wl)
            server = ServingServer(hps, vocab, decoder=sim, registry=reg)
        # enqueue EVERYTHING before the dispatch thread exists: arrival
        # order is the committed mix, group/slot assignment is pure FIFO
        futs = [server.submit(a, uuid=f"u{i}")
                for i, a in enumerate(_articles(wl))]
        server.start()
        results = [f.result(timeout=120) for f in futs]
        server.stop()
    # exactly-once, every request, in both modes
    assert [r.uuid for r in results] == \
        [f"u{i}" for i in range(wl["requests"])]
    assert set(sim.vresolve) == {f"u{i}" for i in range(wl["requests"])}
    return sim.vresolve, reg, sim


def _p99(latencies):
    xs = sorted(latencies)
    return xs[min(len(xs) - 1, int(len(xs) * 0.99))]


@pytest.fixture(scope="module")
def measured(slo):
    wl = slo["workload"]
    cont_resolve, cont_reg, _ = _run_mode(wl, "continuous")
    micro_resolve, _, micro_sim = _run_mode(wl, "microbatch")
    return {
        "cont_p99": _p99(cont_resolve.values()),
        "cont_occupancy": cont_reg.histogram("serve/slot_occupancy").mean,
        "micro_p99": _p99(micro_resolve.values()),
        "micro_utilization": (sum(micro_sim.utilizations)
                              / len(micro_sim.utilizations)),
    }


def test_continuous_p99_within_committed_ceiling(slo, measured):
    ceiling = slo["continuous"]["p99_virtual_ms_max"]
    assert measured["cont_p99"] <= ceiling, (
        f"continuous p99 rose to {measured['cont_p99']:.0f} virtual ms "
        f"(committed ceiling {ceiling:.0f}) — the slot scheduler "
        f"regressed (see SERVE_SLO.json _comment)")


def test_continuous_occupancy_above_committed_floor(slo, measured):
    floor = slo["continuous"]["occupancy_mean_min"]
    assert measured["cont_occupancy"] >= floor, (
        f"continuous mean slot occupancy fell to "
        f"{measured['cont_occupancy']:.2f} (committed floor {floor:.2f}) "
        f"— refill is not keeping slots busy")


def test_continuous_beats_microbatch_p99(slo, measured):
    ratio_max = slo["vs_microbatch"]["p99_ratio_max"]
    ratio = measured["cont_p99"] / measured["micro_p99"]
    assert ratio <= ratio_max, (
        f"continuous p99 / micro-batch p99 = {ratio:.2f} (committed max "
        f"{ratio_max:.2f}) on the bimodal load — the barrier win eroded")


def test_continuous_beats_microbatch_occupancy(slo, measured):
    adv_min = slo["vs_microbatch"]["occupancy_advantage_min"]
    adv = measured["cont_occupancy"] / measured["micro_utilization"]
    assert adv >= adv_min, (
        f"continuous occupancy / micro-batch utilization = {adv:.2f} "
        f"(committed min {adv_min:.2f}) — slot recycling no longer "
        f"recovers the straggler waste")


# -- prefill/decode disaggregation (ISSUE 11) ------------------------------
#
# Same virtual-time discipline, new claim: under the committed bimodal
# mix, DISAGGREGATION (bucketed prefill + length-masked chunks, the
# DisaggSimEngine cost model, driven through the REAL ContinuousBatcher
# prefill queue) beats the pre-change one-resident-shape cost model
# (UniformSimEngine) on SHORT-request p50 while long-request-dominated
# p99 stays pinned — short articles stop paying long articles' shapes,
# and nobody pays more.


def _run_disagg(slo, engine_cls):
    wl = dict(slo["workload"])
    wl.update(slo["disaggregated"]["workload"])
    vocab = Vocab(words=WORDS)
    hps = HParams(
        mode="decode", batch_size=wl["batch_size"], vocab_size=vocab.size(),
        max_enc_steps=wl["long_words"], max_dec_steps=wl["long_steps"],
        beam_size=2, min_dec_steps=1, max_oov_buckets=4,
        serve_max_queue=max(4 * wl["requests"], 64),
        serve_mode="continuous", serve_slots=wl["slots"],
        serve_refill_chunk=wl["chunk"],
        serve_prefill_depth=wl["prefill_depth"])
    arts = _articles(wl)
    short = {f"u{i}" for i, a in enumerate(arts)
             if len(a.split()) <= wl["short_words"]}
    with obs.use_registry(Registry()) as reg:
        sim = engine_cls(wl)
        server = ServingServer(hps, vocab, decoder=_NullDecoder(),
                               engine=sim, registry=reg)
        futs = [server.submit(a, uuid=f"u{i}") for i, a in enumerate(arts)]
        server.start()
        results = [f.result(timeout=120) for f in futs]
        server.stop()
    assert [r.uuid for r in results] == \
        [f"u{i}" for i in range(wl["requests"])]
    assert set(sim.vresolve) == {f"u{i}" for i in range(wl["requests"])}
    return sim.vresolve, short, reg


@pytest.fixture(scope="module")
def disagg_measured(slo):
    dis_resolve, short, dis_reg = _run_disagg(slo, DisaggSimEngine)
    uni_resolve, _, _ = _run_disagg(slo, UniformSimEngine)

    def p50(resolve, keys):
        xs = sorted(resolve[k] for k in keys)
        return xs[len(xs) // 2]

    return {
        "dis_short_p50": p50(dis_resolve, short),
        "uni_short_p50": p50(uni_resolve, short),
        "dis_p99": _p99(dis_resolve.values()),
        "uni_p99": _p99(uni_resolve.values()),
        "prefills": dis_reg.counter("serve/prefill_total").value,
        "prefill_bucket_mean":
            dis_reg.histogram("serve/prefill_bucket_len").mean,
        "requests": len(dis_resolve),
    }


def test_disagg_short_p50_beats_uniform_baseline(slo, disagg_measured):
    ceiling = slo["disaggregated"]["short_p50_ratio_vs_uniform_max"]
    ratio = disagg_measured["dis_short_p50"] \
        / disagg_measured["uni_short_p50"]
    assert ratio <= ceiling, (
        f"disaggregated short-request p50 / uniform-padding baseline = "
        f"{ratio:.2f} (committed max {ceiling:.2f}) on the bimodal mix — "
        f"short articles are paying long articles' shapes again "
        f"(see SERVE_SLO.json disaggregated._comment)")
    abs_ceiling = slo["disaggregated"]["short_p50_virtual_ms_max"]
    assert disagg_measured["dis_short_p50"] <= abs_ceiling, (
        f"disaggregated short-request p50 rose to "
        f"{disagg_measured['dis_short_p50']:.0f} virtual ms (committed "
        f"ceiling {abs_ceiling:.0f})")


def test_disagg_p99_stays_pinned(slo, disagg_measured):
    """The 'at fixed p99' half of the claim: the tail (long-request
    dominated) must not regress past the committed ratio — prefill
    serialization on the dispatch thread cannot be bought with tail
    latency."""
    ceiling = slo["disaggregated"]["p99_ratio_vs_uniform_max"]
    ratio = disagg_measured["dis_p99"] / disagg_measured["uni_p99"]
    assert ratio <= ceiling, (
        f"disaggregated p99 / uniform baseline p99 = {ratio:.2f} "
        f"(committed max {ceiling:.2f}) — the disaggregated path "
        f"regressed the tail")


def test_disagg_runs_through_the_real_prefill_queue(slo, disagg_measured):
    """The gate drives the REAL ContinuousBatcher: every request went
    through the prefill stage exactly once, and the mean prefill bucket
    sits strictly below the top bucket (short articles really routed to
    short encoder shapes)."""
    wl = dict(slo["workload"])
    wl.update(slo["disaggregated"]["workload"])
    assert disagg_measured["prefills"] == disagg_measured["requests"]
    assert disagg_measured["prefill_bucket_mean"] < wl["long_words"]


# -- paged resident state (ISSUE 20) ---------------------------------------
#
# Memory-capped comparison under the same virtual cost model and bimodal
# mix: a FIXED page budget (paged.workload.arena_pages) either holds
# every slot at full length (arena_pages // pages_per_long slots — what
# an engine with no arena option reserves, and no admission ever waits
# for pages) or backs `paged_slots` slots admitted by FREE PAGES (the
# ISSUE 20 tight arena, driven through the REAL ContinuousBatcher's
# arena admission).  The committed claim: at the same memory, the
# tight-arena run ("paged" in SERVE_SLO.json) holds >=
# resident_advantage_min x the full-length run's ("dense") mean
# resident count AND resolves the load with LOWER p99 — capacity bought with paging, not latency
# bought with memory.  The arena is deliberately sized so the mix
# cannot always fit (paged_slots x pages_per_long > arena_pages), so
# the run also proves the backpressure contract end-to-end: allocation
# failures are counted and REQUEUED (exactly-once resolution still
# asserted for all requests), and the arena drains to zero in-use pages
# once the load completes.


class PagedSimEngine(DisaggSimEngine):
    """DisaggSimEngine + the ISSUE 20 arena surface
    (``pages_needed``/``free_pages``/``arena_stats``): pack allocates
    ceil(words / page_words) pages, harvest/release frees them.  pack
    raises the typed ArenaExhaustedError on shortfall — the batcher's
    proactive free-page admission should make that unreachable, and the
    SLO run asserts it stays that way (requeues happen at the admission
    check, never as a failed pack)."""

    def __init__(self, wl):
        super().__init__(wl)
        self._capacity = wl["arena_pages"]
        self._page_words = wl["page_words"]
        self._slot_pages = [0] * self.slots
        self._in_use = 0
        self.pack_shortfalls = 0

    def _pages(self, words: int) -> int:
        return max(1, -(-int(words) // self._page_words))

    def pages_needed(self, pre) -> int:
        return self._pages(pre.example.enc_len)

    def free_pages(self) -> int:
        return self._capacity - self._in_use

    def arena_stats(self):
        return {"capacity": self._capacity, "free": self.free_pages(),
                "in_use": self._in_use,
                "fill": self._in_use / self._capacity}

    def pack(self, idx, pre):
        need = self._pages(pre.words)
        if need > self.free_pages():
            self.pack_shortfalls += 1
            raise ArenaExhaustedError(
                f"sim arena exhausted: need {need}, "
                f"free {self.free_pages()}",
                needed=need, free=self.free_pages())
        self._in_use += need
        self._slot_pages[idx] = need
        super().pack(idx, pre)

    def _free_slot_pages(self, idx):
        self._in_use -= self._slot_pages[idx]
        self._slot_pages[idx] = 0

    def unpack(self, idx, example):
        res = super().unpack(idx, example)
        self._free_slot_pages(idx)
        return res

    def release(self, idx):
        super().release(idx)
        self._free_slot_pages(idx)


def _run_paged(slo, paged: bool):
    """Drive the bimodal load at a fixed page budget: paged=False
    holds every slot at full length (arena_pages // pages_per_long
    slots: the arena can never run out), paged=True serves paged_slots
    slots from the same pages.  Returns (vresolve, registry, sim,
    slots)."""
    wl = dict(slo["workload"])
    wl.update(slo["disaggregated"]["workload"])
    wl.update(slo["paged"]["workload"])
    pages_per_long = -(-wl["long_words"] // wl["page_words"])
    slots = wl["paged_slots"] if paged \
        else wl["arena_pages"] // pages_per_long
    wl["slots"] = slots
    vocab = Vocab(words=WORDS)
    hps = HParams(
        mode="decode", batch_size=wl["batch_size"], vocab_size=vocab.size(),
        max_enc_steps=wl["long_words"], max_dec_steps=wl["long_steps"],
        beam_size=2, min_dec_steps=1, max_oov_buckets=4,
        serve_max_queue=max(4 * wl["requests"], 64),
        serve_mode="continuous", serve_slots=slots,
        serve_refill_chunk=wl["chunk"],
        serve_prefill_depth=wl["prefill_depth"])
    arts = _articles(wl)
    with obs.use_registry(Registry()) as reg:
        sim = PagedSimEngine(wl)
        server = ServingServer(hps, vocab, decoder=_NullDecoder(),
                               engine=sim, registry=reg)
        futs = [server.submit(a, uuid=f"u{i}") for i, a in enumerate(arts)]
        server.start()
        results = [f.result(timeout=120) for f in futs]
        server.stop()
    # exactly-once under backpressure: every requeued admission still
    # resolves, once, with its own uuid
    assert [r.uuid for r in results] == \
        [f"u{i}" for i in range(wl["requests"])]
    assert set(sim.vresolve) == {f"u{i}" for i in range(wl["requests"])}
    return sim.vresolve, reg, sim, slots


@pytest.fixture(scope="module")
def paged_measured(slo):
    paged_resolve, paged_reg, paged_sim, paged_slots = _run_paged(slo, True)
    dense_resolve, dense_reg, _, dense_slots = _run_paged(slo, False)
    paged_occ = paged_reg.histogram("serve/slot_occupancy")
    dense_occ = dense_reg.histogram("serve/slot_occupancy")
    return {
        "paged_p99": _p99(paged_resolve.values()),
        "dense_p99": _p99(dense_resolve.values()),
        "paged_peak_residents": paged_occ.percentile(100) * paged_slots,
        "dense_peak_residents": dense_occ.percentile(100) * dense_slots,
        "paged_mean_residents": paged_occ.mean * paged_slots,
        "dense_mean_residents": dense_occ.mean * dense_slots,
        "alloc_failures":
            paged_reg.counter("serve/arena_alloc_failures_total").value,
        "fill_observations": paged_reg.histogram("serve/arena_fill").count,
        "peak_fill": paged_reg.histogram("serve/arena_fill").percentile(100),
        "pack_shortfalls": paged_sim.pack_shortfalls,
        "final_in_use": paged_sim.arena_stats()["in_use"],
        "dense_alloc_failures":
            dense_reg.counter("serve/arena_alloc_failures_total").value,
    }


def test_paged_resident_advantage_at_fixed_memory(slo, paged_measured):
    """The capacity claim, both edges: the arena actually REACHES >=
    resident_advantage_min x the dense resident ceiling at the same
    page budget (peak concurrent residents — memory the dense layout
    simply cannot hold), and holds the advantage on the run's MEAN
    (drain tail included) above its own floor."""
    floor = slo["paged"]["resident_advantage_min"]
    adv = paged_measured["paged_peak_residents"] \
        / paged_measured["dense_peak_residents"]
    assert adv >= floor, (
        f"paged peak residents / dense peak residents = {adv:.2f} at the "
        f"same page budget (committed min {floor:.2f}) — the arena is no "
        f"longer converting block granularity into resident capacity "
        f"(see SERVE_SLO.json paged._comment)")
    mean_floor = slo["paged"]["mean_resident_advantage_min"]
    mean_adv = paged_measured["paged_mean_residents"] \
        / paged_measured["dense_mean_residents"]
    assert mean_adv >= mean_floor, (
        f"paged mean residents / dense mean residents = {mean_adv:.2f} "
        f"(committed min {mean_floor:.2f}) — the peak is reached but not "
        f"held across the run")


def test_paged_p99_beats_dense_at_fixed_memory(slo, paged_measured):
    ceiling = slo["paged"]["p99_ratio_vs_dense_max"]
    ratio = paged_measured["paged_p99"] / paged_measured["dense_p99"]
    assert ratio <= ceiling, (
        f"paged p99 / dense-memory-equivalent p99 = {ratio:.2f} "
        f"(committed max {ceiling:.2f}) — the extra residents are no "
        f"longer buying latency on the bimodal mix")


def test_paged_backpressure_requeues_and_drains(slo, paged_measured):
    """The arena is sized so the mix cannot always fit: the committed
    minimum of admission-blocked events must fire (each one a REQUEUE —
    exactly-once is asserted inside the run), pack itself must never
    see a shortfall (the proactive admission check catches them all),
    the fill series must be lit with a full-arena episode observed, and
    the arena must drain to zero once the load completes (no leaked
    pages across harvest/recycle churn)."""
    assert paged_measured["alloc_failures"] >= \
        slo["paged"]["min_backpressure_events"]
    assert paged_measured["pack_shortfalls"] == 0
    assert paged_measured["fill_observations"] > 0
    assert paged_measured["peak_fill"] >= \
        slo["paged"]["min_peak_arena_fill"]
    assert paged_measured["final_in_use"] == 0
    # every slot at full length: no admission ever waits for pages
    assert paged_measured["dense_alloc_failures"] == 0


# -- elastic serving fleet (ISSUE 13) --------------------------------------
#
# Fleet-level virtual time: the REAL FleetRouter + ServingServers +
# ContinuousBatchers, driven single-threaded over a shared round clock
# (one round = every live replica ticks once, in parallel; the clock
# advances chunk * step_cost_ms per round) with deterministic arrivals.
# Routing decisions, hedge timing, the rolling-swap state machine, and
# the replica-kill requeue path are all exact scheduling facts — see
# SERVE_SLO.json "fleet" _comment for the committed scenarios.


class _VClock:
    """The fleet's shared virtual clock, advanced by the round driver
    (replicas run concurrently, so ONE advance per round, not one per
    replica tick)."""

    def __init__(self):
        self.ms = 0.0

    def now(self) -> float:  # seconds, the router's clock unit
        return self.ms / 1000.0


class FleetSimEngine(NoArena):
    """SlotDecodeEngine-protocol sim over the SHARED fleet clock.
    ``speed`` < 1 models a degraded replica (the hedge scenario's
    straggler source): its residents advance speed * chunk steps per
    round while healthy neighbors advance the full chunk."""

    def __init__(self, wl, vclock, speed: float = 1.0):
        self.slots = wl["slots"]
        self.chunk = wl["chunk"]
        self.speed = speed
        self._wl = wl
        self._vclock = vclock
        self._remaining = [0.0] * self.slots
        self._active = [False] * self.slots
        self.vresolve = {}

    def pack(self, idx, example):
        assert not self._active[idx]
        self._active[idx] = True
        self._remaining[idx] = _steps_for(example, self._wl)

    def step(self):
        fin = []
        for i in range(self.slots):
            if self._active[i]:
                self._remaining[i] -= self.chunk * self.speed
                if self._remaining[i] <= 0:
                    fin.append(i)
        return fin

    def unpack(self, idx, example):
        assert self._active[idx]
        self._active[idx] = False
        # first-wins: a hedged uuid may unpack on two replicas; the
        # caller observed the EARLIER one
        prev = self.vresolve.get(example.uuid)
        if prev is None or self._vclock.ms < prev:
            self.vresolve[example.uuid] = self._vclock.ms
        return DecodedResult(
            uuid=example.uuid, article=example.original_article,
            decoded_words=["ok", "."], reference=example.reference,
            abstract_sents=[])

    def release(self, idx):
        self._active[idx] = False


def _run_fleet(slo, swap: bool = False, kill: bool = False,
               slow: bool = False):
    """Drive the committed fleet workload through the REAL router;
    returns (per-uuid virtual resolve times, fleet registry, captured
    request events, results)."""
    from textsummarization_on_flink_tpu.obs.export import MemorySink
    from textsummarization_on_flink_tpu.serve.fleet import FleetRouter

    wl = slo["fleet"]["workload"]
    vocab = Vocab(words=WORDS)
    vclock = _VClock()
    hps = HParams(
        mode="decode", batch_size=wl["slots"], vocab_size=vocab.size(),
        max_enc_steps=wl["long_words"], max_dec_steps=wl["long_steps"],
        beam_size=2, min_dec_steps=1, max_oov_buckets=4,
        serve_max_queue=max(4 * wl["requests"], 64),
        serve_mode="continuous", serve_slots=wl["slots"],
        serve_refill_chunk=wl["chunk"],
        serve_hedge_ms=wl["hedge_ms"],
        serve_hedge_max_ratio=wl["hedge_max_ratio"])
    fleet_reg = Registry()
    sink = MemorySink()
    fleet_reg.event_sink = sink
    servers, engines = [], []
    for r in range(wl["replicas"]):
        eng = FleetSimEngine(
            wl, vclock,
            speed=wl["slow_factor"] if (slow and r == 0) else 1.0)
        servers.append(ServingServer(
            hps, vocab, decoder=_NullDecoder(), engine=eng,
            registry=Registry()))
        engines.append(eng)
    router = FleetRouter(servers, hps, registry=fleet_reg,
                         clock=vclock.now)
    arts = _articles({**slo["workload"], **wl})
    futs, i, rounds = [], 0, 0
    while True:
        rounds += 1
        assert rounds < 5000, "fleet virtual run did not converge"
        for _ in range(wl["arrive_per_round"]):
            if i < len(arts):
                futs.append(router.submit(arts[i], uuid=f"u{i}"))
                i += 1
        if kill and rounds == wl["kill_round"]:
            alive = [h for h in router.replicas() if not h.killed]
            victim = max(alive, key=lambda h: h.load())
            assert victim.server.load() > 0, \
                "kill scenario must catch the victim mid-decode"
            router.kill_replica(victim.rid)
        if swap and rounds == wl["swap_start_round"] \
                and not router.swap_active() \
                and not fleet_reg.counter("serve/fleet_swaps_total").value:
            router.start_rolling_swap()
        router.tick()
        for srv, h in zip(servers, router.replicas()):
            if not h.killed:
                srv.tick_once(poll=0.0)
        vclock.ms += wl["chunk"] * wl["step_cost_ms"]
        if i >= len(arts) and all(f.done() for f in futs) \
                and not router.swap_active():
            break
    results = [f.result(timeout=0) for f in futs]
    router.stop()
    # exactly-once, fleet-level: one result per admitted uuid, in order
    assert [r.uuid for r in results] == \
        [f"u{k}" for k in range(wl["requests"])]
    resolve = {}
    for eng in engines:
        for u, t in eng.vresolve.items():
            resolve[u] = min(resolve.get(u, t), t)
    assert set(resolve) == {f"u{k}" for k in range(wl["requests"])}
    events = [r for r in sink.records() if r.get("kind") == "request"]
    return resolve, fleet_reg, events, results


@pytest.fixture(scope="module")
def fleet_measured(slo):
    steady_resolve, steady_reg, _, _ = _run_fleet(slo)
    swap_resolve, swap_reg, _, _ = _run_fleet(slo, swap=True)
    return {
        "steady_p99": _p99(steady_resolve.values()),
        "swap_p99": _p99(swap_resolve.values()),
        "swaps": swap_reg.counter("serve/fleet_swaps_total").value,
    }


def test_fleet_steady_p99_within_committed_ceiling(slo, fleet_measured):
    ceiling = slo["fleet"]["steady_p99_virtual_ms_max"]
    assert fleet_measured["steady_p99"] <= ceiling, (
        f"fleet steady-state p99 rose to {fleet_measured['steady_p99']:.0f}"
        f" virtual ms (committed ceiling {ceiling:.0f}) — routing or the "
        f"round scheduler regressed (see SERVE_SLO.json fleet._comment)")


def test_fleet_rolling_swap_p99_within_committed_ratio(slo, fleet_measured):
    """The upgrade tax: a replica-at-a-time drain -> hot-swap -> readmit
    pass must not cost the fleet more than the committed p99 ratio over
    steady state — and the swap must actually visit every replica."""
    ratio_max = slo["fleet"]["swap_p99_ratio_max"]
    ratio = fleet_measured["swap_p99"] / fleet_measured["steady_p99"]
    assert ratio <= ratio_max, (
        f"fleet p99 under rolling swap / steady-state p99 = {ratio:.2f} "
        f"(committed max {ratio_max:.2f}) — draining one replica at a "
        f"time is costing more than the committed upgrade tax")
    assert fleet_measured["swaps"] == slo["fleet"]["swap_count_expected"], (
        f"rolling swap completed {fleet_measured['swaps']:.0f} of "
        f"{slo['fleet']['swap_count_expected']} replica hot-swaps")


def test_fleet_hedge_wins_counted_and_rate_capped(slo):
    """Hedging must PAY (a degraded replica's stragglers resolve from
    their hedge twins) and must stay CAPPED (a hedge is a purchased
    duplicate; spend rides the committed serve_hedge_max_ratio
    ceiling)."""
    _, reg, _, _ = _run_fleet(slo, slow=True)
    hedges = reg.counter("serve/hedges_total").value
    wins = reg.counter("serve/hedge_wins_total").value
    submitted = reg.counter("serve/fleet_submitted_total").value
    assert wins >= slo["fleet"]["hedge_wins_min"], (
        f"only {wins:.0f} hedge wins against the slow replica (committed "
        f"min {slo['fleet']['hedge_wins_min']}) — hedging stopped paying")
    assert hedges >= wins, "a hedge win without a hedge is an accounting bug"
    rate = hedges / submitted
    assert rate <= slo["fleet"]["hedge_rate_max"], (
        f"hedge rate {rate:.3f} exceeds the committed ceiling "
        f"{slo['fleet']['hedge_rate_max']} — the waste cap broke")


# -- the production front door (ISSUE 14) ----------------------------------
#
# Same virtual-time discipline, front-door claims: under a ZIPF request
# mix (the heavy-tailed trending-article shape) the coalescing map and
# the summary cache cut served decodes far below submitted requests at
# a p99 no worse than the uncached baseline, every coalesced/cached
# future resolves exactly once, and the per-tenant token bucket +
# weighted-fair pickup isolate a victim tenant from an attacker
# flooding at 10x its admitted rate.  All three scenarios drive the
# REAL RequestQueue/ContinuousBatcher/ServingServer (and, in the fleet
# scenario, the REAL FleetRouter) — the front door is the only new
# layer in the path.


def _zipf_indices(n: int, pool: int, s: float, seed: int):
    """Deterministic zipf-ish draw: p(k) ~ 1/(k+1)^s over `pool` ranks
    (inverse-CDF over a seeded uniform stream — no numpy, exactly
    replayable)."""
    weights = [1.0 / (k + 1) ** s for k in range(pool)]
    total = sum(weights)
    r = random.Random(seed)
    out = []
    for _ in range(n):
        x = r.random() * total
        acc = 0.0
        pick = pool - 1
        for k, w in enumerate(weights):
            acc += w
            if x <= acc:
                pick = k
                break
        out.append(pick)
    return out


def _door_articles(wl):
    """The zipf article pool: `pool` DISTINCT articles (distinct lead
    token -> distinct content hash), every long_every-th one long."""
    arts = []
    for k in range(wl["pool"]):
        n = wl["long_words"] if k % wl["long_every"] == 0 \
            else wl["short_words"]
        arts.append(f"a{k} " + " ".join(["w"] * (n - 1)))
    return arts


class CountingSimEngine(SimEngine):
    """SimEngine + the decode count the front-door ratio gates on
    (packs == decodes actually served by the engine)."""

    def __init__(self, wl):
        super().__init__(wl)
        self.pack_count = 0

    def pack(self, idx, example):
        super().pack(idx, example)
        self.pack_count += 1


def _run_front_door(slo, door: bool):
    """Drive the zipf mix through a real continuous ServingServer with
    the front door armed (`door`) or off (the uncached baseline);
    returns (per-uuid resolve vtimes, registry, engine, hit count)."""
    wl = {**slo["workload"], **slo["front_door"]["workload"]}
    vocab = Vocab(words=WORDS)
    hps = HParams(
        mode="decode", batch_size=wl["slots"], vocab_size=vocab.size(),
        max_enc_steps=wl["long_words"], max_dec_steps=wl["long_steps"],
        beam_size=2, min_dec_steps=1, max_oov_buckets=4,
        serve_max_queue=max(4 * wl["requests"], 64),
        serve_mode="continuous", serve_slots=wl["slots"],
        serve_refill_chunk=wl["chunk"],
        serve_coalesce=door,
        serve_cache_entries=wl["cache_entries"] if door else 0)
    arts = _door_articles(wl)
    order = _zipf_indices(wl["requests"], wl["pool"], wl["zipf_s"],
                          wl["seed"])
    with obs.use_registry(Registry()) as reg:
        sim = CountingSimEngine(wl)
        server = ServingServer(hps, vocab, decoder=_NullDecoder(),
                               engine=sim, registry=reg)
        resolve_v: dict = {}

        def submit(uid, art):
            fut = server.submit(art, uuid=uid)
            fut.add_done_callback(
                lambda f, u=uid: resolve_v.setdefault(u, sim.vtime))
            return fut

        # wave 1: the whole zipf mix enqueued BEFORE the dispatch
        # thread starts (arrival order committed; duplicates coalesce
        # onto the one queued leader per distinct article)
        futs = [submit(f"u{i}", arts[k]) for i, k in enumerate(order)]
        server.start()
        results = [f.result(timeout=120) for f in futs]
        # exactly-once, every submit — followers included
        assert [r.uuid for r in results] == \
            [f"u{i}" for i in range(wl["requests"])]
        assert set(resolve_v) == {f"u{i}" for i in range(wl["requests"])}
        hits0 = reg.counter("serve/cache_hits_total").value
        if door:
            # wave 2: the same mix again, against a now-warm cache —
            # every request resolves synchronously at submit, zero new
            # decodes (the dispatch thread is idle and stays idle)
            packs0 = sim.pack_count
            futs2 = [submit(f"w{i}", arts[k]) for i, k in enumerate(order)]
            res2 = [f.result(timeout=10) for f in futs2]
            assert [r.uuid for r in res2] == \
                [f"w{i}" for i in range(wl["requests"])]
            assert sim.pack_count == packs0, \
                "a warm-cache wave must not decode"
            assert reg.counter("serve/cache_hits_total").value \
                == hits0 + wl["requests"]
            # a cached summary is the leader's payload verbatim: every
            # duplicate of article k carries identical decoded words
            by_article: dict = {}
            for i, k in enumerate(order):
                by_article.setdefault(k, set()).add(
                    " ".join(res2[i].decoded_words))
            assert all(len(v) == 1 for v in by_article.values())
        server.stop()
    return resolve_v, reg, sim, hits0


@pytest.fixture(scope="module")
def front_door_measured(slo):
    on_resolve, on_reg, on_sim, _ = _run_front_door(slo, door=True)
    off_resolve, _, off_sim, _ = _run_front_door(slo, door=False)
    wl = {**slo["workload"], **slo["front_door"]["workload"]}
    return {
        "decodes_on": on_sim.pack_count,
        "decodes_off": off_sim.pack_count,
        "coalesced": on_reg.counter("serve/coalesced_total").value,
        "p99_on": _p99(on_resolve.values()),
        "p99_off": _p99(off_resolve.values()),
        "requests": wl["requests"],
    }


def test_front_door_decodes_per_submit_under_ceiling(slo,
                                                     front_door_measured):
    """The FastSeq claim, gated: under the committed zipf mix the
    coalescing map alone holds served decodes at the DISTINCT-article
    count — far under the committed <= 0.5x submitted ceiling — while
    the uncached baseline decodes every submit."""
    m = front_door_measured
    ceiling = slo["front_door"]["decodes_per_submit_max"]
    ratio = m["decodes_on"] / m["requests"]
    assert ratio <= ceiling, (
        f"front door served {m['decodes_on']} decodes for "
        f"{m['requests']} submits (ratio {ratio:.2f}, committed max "
        f"{ceiling}) — coalescing/caching stopped deduplicating")
    assert m["decodes_off"] == m["requests"], \
        "the uncached baseline must decode every submit"
    assert m["coalesced"] >= m["requests"] - m["decodes_on"] - \
        slo["front_door"]["workload"]["pool"]


def test_front_door_p99_no_worse_than_uncached(slo, front_door_measured):
    """'Never doing redundant work' must not be bought with tail
    latency: zipf-mix p99 with the door armed stays within the
    committed ratio of the uncached baseline (< 1 in practice — fewer
    decodes drain the slots sooner)."""
    m = front_door_measured
    ratio_max = slo["front_door"]["p99_ratio_vs_uncached_max"]
    ratio = m["p99_on"] / m["p99_off"]
    assert ratio <= ratio_max, (
        f"front-door p99 / uncached p99 = {ratio:.2f} (committed max "
        f"{ratio_max:.2f}) on the zipf mix — the door is adding tail "
        f"latency instead of removing work")


def _run_tenants(slo, attacker: bool):
    """The cross-tenant isolation scenario, tick-driven (no threads):
    a victim tenant trickles short articles while an attacker floods at
    10x its admitted rate; the per-tenant token bucket sheds the excess
    typed BEFORE the queue and weighted-fair pickup keeps the victim's
    latency flat.  Returns (victim latencies vms, sheds, registry)."""
    from textsummarization_on_flink_tpu.serve.errors import (
        TenantThrottledError,
    )

    wl = {**slo["workload"], **slo["front_door"]["tenants"]}
    vocab = Vocab(words=WORDS)
    vclock = _VClock()
    hps = HParams(
        mode="decode", batch_size=wl["slots"], vocab_size=vocab.size(),
        max_enc_steps=wl["long_words"], max_dec_steps=wl["long_steps"],
        beam_size=2, min_dec_steps=1, max_oov_buckets=4,
        serve_max_queue=wl["queue"],
        serve_mode="continuous", serve_slots=wl["slots"],
        serve_refill_chunk=wl["chunk"],
        serve_tenant_rate=wl["tenant_rate"],
        serve_tenant_burst=wl["tenant_burst"],
        serve_fair_weights=wl["fair_weights"])
    with obs.use_registry(Registry()) as reg:
        sim = CountingSimEngine(wl)
        server = ServingServer(hps, vocab, decoder=_NullDecoder(),
                               engine=sim, registry=reg, clock=vclock.now)
        submit_v: dict = {}
        resolve_v: dict = {}
        victim_futs = []
        sheds = 0
        n_v = 0

        def track(fut, uid):
            fut.add_done_callback(
                lambda f, u=uid: resolve_v.setdefault(u, sim.vtime))

        for rnd in range(wl["rounds"]):
            if rnd % wl["victim_every"] == 0:
                uid = f"v{n_v}"
                n_v += 1
                art = f"{uid} " + " ".join(["w"] * (wl["short_words"] - 1))
                fut = server.submit(art, uuid=uid, tenant="victim")
                submit_v[uid] = sim.vtime
                track(fut, uid)
                victim_futs.append((uid, fut))
            if attacker:
                for j in range(wl["attacker_per_round"]):
                    uid = f"x{rnd}_{j}"
                    art = f"{uid} " + \
                        " ".join(["w"] * (wl["short_words"] - 1))
                    try:
                        server.submit(art, uuid=uid, tenant="attacker")
                    except TenantThrottledError:
                        sheds += 1  # the typed outcome: shed at the door
            server.tick_once(poll=0.0)
            vclock.ms += wl["chunk"] * wl["step_cost_ms"]
        # drain: every admitted request must still resolve exactly once
        for _ in range(1000):
            if all(f.done() for _, f in victim_futs):
                break
            server.tick_once(poll=0.0)
            vclock.ms += wl["chunk"] * wl["step_cost_ms"]
        results = [f.result(timeout=0) for _, f in victim_futs]
        server.stop()
    assert [r.uuid for r in results] == [u for u, _ in victim_futs]
    lat = [resolve_v[u] - submit_v[u] for u, _ in victim_futs]
    return lat, sheds, reg


@pytest.fixture(scope="module")
def tenants_measured(slo):
    flood_lat, sheds, flood_reg = _run_tenants(slo, attacker=True)
    solo_lat, _, _ = _run_tenants(slo, attacker=False)
    return {
        "victim_p99_flood": _p99(flood_lat),
        "victim_p99_solo": _p99(solo_lat),
        "sheds": sheds,
        "shed_total": flood_reg.counter("serve/tenant_shed_total").value,
    }


def test_tenant_isolation_victim_p99_flat(slo, tenants_measured):
    """The cross-tenant isolation gate (ISSUE 14 acceptance): with an
    attacker tenant flooding at 10x its admitted rate, the victim
    tenant's p99 stays within the committed ratio of its
    attacker-free steady state."""
    m = tenants_measured
    ratio_max = slo["front_door"]["tenants"]["victim_p99_ratio_max"]
    ratio = m["victim_p99_flood"] / max(m["victim_p99_solo"], 1e-9)
    assert ratio <= ratio_max, (
        f"victim p99 under attacker flood = {m['victim_p99_flood']:.0f} "
        f"vms vs {m['victim_p99_solo']:.0f} steady (ratio {ratio:.2f}, "
        f"committed max {ratio_max}) — tenant isolation broke")


def test_tenant_flood_shed_typed_at_the_door(slo, tenants_measured):
    """The attacker's excess is shed TYPED by its own token bucket
    (TenantThrottledError, counted in serve/tenant_shed_total) before
    ever touching the shared queue — the victim spends nothing on it."""
    m = tenants_measured
    floor = slo["front_door"]["tenants"]["sheds_min"]
    assert m["sheds"] >= floor, (
        f"only {m['sheds']} attacker submits shed (committed min "
        f"{floor}) — the token bucket is not metering the flood")
    assert m["shed_total"] == m["sheds"]


class CountingFleetSimEngine(FleetSimEngine):
    """FleetSimEngine + pack counting for the fleet front-door ratio."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.pack_count = 0

    def pack(self, idx, example):
        super().pack(idx, example)
        self.pack_count += 1


def _run_fleet_door(slo, kill: bool):
    """The zipf mix through the REAL FleetRouter with the front door
    armed at the ROUTER (replica doors disarmed by construction) —
    coalescing dedups ACROSS replicas, and a replica killed mid-
    coalesced-flight requeues the LEADER while every attached follower
    still resolves exactly once from whichever replica wins."""
    from textsummarization_on_flink_tpu.serve.fleet import FleetRouter

    wl = {**slo["fleet"]["workload"], **slo["front_door"]["fleet"]}
    vocab = Vocab(words=WORDS)
    vclock = _VClock()
    hps = HParams(
        mode="decode", batch_size=wl["slots"], vocab_size=vocab.size(),
        max_enc_steps=wl["long_words"], max_dec_steps=wl["long_steps"],
        beam_size=2, min_dec_steps=1, max_oov_buckets=4,
        serve_max_queue=max(4 * wl["requests"], 64),
        serve_mode="continuous", serve_slots=wl["slots"],
        serve_refill_chunk=wl["chunk"],
        serve_hedge_ms=wl["hedge_ms"],
        serve_hedge_max_ratio=wl["hedge_max_ratio"],
        serve_coalesce=True, serve_cache_entries=wl["cache_entries"])
    fleet_reg = Registry()
    servers, engines = [], []
    for _ in range(wl["replicas"]):
        eng = CountingFleetSimEngine(wl, vclock)
        servers.append(ServingServer(
            hps, vocab, decoder=_NullDecoder(), engine=eng,
            registry=Registry()))
        engines.append(eng)
    router = FleetRouter(servers, hps, registry=fleet_reg,
                         clock=vclock.now)
    arts = _door_articles(wl)
    order = _zipf_indices(wl["requests"], wl["pool"], wl["zipf_s"],
                          wl["seed"])
    futs, i, rounds = [], 0, 0
    while True:
        rounds += 1
        assert rounds < 5000, "fleet front-door run did not converge"
        for _ in range(wl["arrive_per_round"]):
            if i < len(order):
                futs.append(router.submit(arts[order[i]], uuid=f"u{i}"))
                i += 1
        if kill and rounds == wl["kill_round"]:
            alive = [h for h in router.replicas() if not h.killed]
            victim = max(alive, key=lambda h: h.load())
            assert victim.server.load() > 0, \
                "kill must catch the victim mid-decode"
            router.kill_replica(victim.rid)
        router.tick()
        for srv, h in zip(servers, router.replicas()):
            if not h.killed:
                srv.tick_once(poll=0.0)
        vclock.ms += wl["chunk"] * wl["step_cost_ms"]
        if i >= len(order) and all(f.done() for f in futs):
            break
    results = [f.result(timeout=0) for f in futs]
    router.stop()
    # fleet-level exactly-once: one RESULT per submitted uuid —
    # leaders, followers, and cache hits alike, kill or no kill
    assert [r.uuid for r in results] == \
        [f"u{k}" for k in range(wl["requests"])]
    decodes = sum(e.pack_count for e in engines)
    return results, fleet_reg, decodes, order


@pytest.fixture(scope="module")
def fleet_door_measured(slo):
    _, reg, decodes, order = _run_fleet_door(slo, kill=False)
    return {
        "decodes": decodes,
        "requests": len(order),
        "coalesced": reg.counter("serve/coalesced_total").value,
        "hits": reg.counter("serve/cache_hits_total").value,
    }


def test_fleet_front_door_dedups_across_replicas(slo, fleet_door_measured):
    """The router-level door is the fleet's ONE dedup point: served
    decodes across ALL replicas stay under the committed ratio, with
    the dedup split between in-flight coalescing and cache hits."""
    m = fleet_door_measured
    ceiling = slo["front_door"]["fleet"]["decodes_per_submit_max"]
    ratio = m["decodes"] / m["requests"]
    assert ratio <= ceiling, (
        f"fleet served {m['decodes']} decodes for {m['requests']} "
        f"submits (ratio {ratio:.2f}, committed max {ceiling}) — "
        f"cross-replica dedup regressed")
    assert m["coalesced"] + m["hits"] >= m["requests"] - m["decodes"]


def test_fleet_front_door_kill_keeps_followers_exactly_once(slo):
    """The chaos composition (ISSUE 14 satellite): serve.replica_kill
    mid-coalesced-flight requeues the LEADER on a survivor and every
    attached follower still resolves exactly once with a RESULT — the
    follower futures ride the router-level leader future, which is
    exactly what the requeue path settles."""
    results, reg, decodes, order = _run_fleet_door(slo, kill=True)
    assert reg.counter("serve/replica_kills_total").value == 1
    assert reg.counter("serve/requeued_total").value >= 1, \
        "the kill landed on an idle replica — not a mid-flight test"
    assert reg.counter("serve/coalesced_total").value >= 1, \
        "no coalesced flight was in the air at the kill"
    assert len({r.uuid for r in results}) == len(order)


def test_fleet_replica_kill_exactly_once_with_requeue(slo):
    """The chaos gate (ISSUE 13 acceptance): a replica killed mid-decode
    under load -> every admitted request still resolves exactly once
    with a RESULT (no lost futures, no double resolution, no
    caller-visible errors), the orphans re-enqueued on survivors through
    the typed path and tagged with `requeued` trace events."""
    resolve, reg, events, results = _run_fleet(slo, kill=True)
    wl = slo["fleet"]["workload"]
    assert reg.counter("serve/replica_kills_total").value == 1
    requeued = reg.counter("serve/requeued_total").value
    assert requeued >= slo["fleet"]["kill_requeued_min"], (
        f"replica death orphaned no requests ({requeued:.0f} requeued) — "
        f"the kill landed on an idle replica, not mid-decode")
    # every requeued request is tagged in the trace stream with the
    # corpse it left and the survivor it landed on
    tags = [e for e in events if e.get("event") == "requeued"]
    assert len(tags) == int(requeued)
    for e in tags:
        assert e["attrs"]["from_replica"] != e["attrs"]["to_replica"]
        assert e["attrs"]["cause"] == "ReplicaKilledError"
    # no admitted request saw the failure: all resolved with results
    assert len(results) == wl["requests"]
    assert len({r.uuid for r in results}) == wl["requests"]


# ---------------------------------------------------------------------------
# Process fleet (ISSUE 17; SERVING.md "Process fleet").  The socket
# transport's costs are BYTE facts, not scheduling facts, so there is
# no virtual clock: the gate prices them analytically off the REAL
# codecs — Message.to_json() frames as the supervisor sends them, reply
# frames out of the real _ReplyHub publish path (seq stamping
# included), and the real obs.http.health() payload at the
# serve_scrape_interval_ms cadence.  Pure construction + arithmetic;
# see SERVE_SLO.json process_fleet._comment for the committed numbers.


def _proc_fleet_requests(wl):
    def words(n, tag):
        return " ".join(f"{tag}{i}" for i in range(n)) + " ."

    reqs = []
    for i in range(wl["requests"]):
        long = (i % wl["long_every"]) == wl["long_every"] - 1
        art = words(wl["long_words"] if long else wl["short_words"], "w")
        reqs.append((f"uuid-{i:04d}", art, f"reference {i} ."))
    return reqs, words(wl["summary_words"], "s")


@pytest.fixture(scope="module")
def proc_fleet_measured(slo):
    from textsummarization_on_flink_tpu.pipeline.io import Message
    from textsummarization_on_flink_tpu.serve import procfleet

    wl = slo["process_fleet"]["workload"]
    reqs, summary = _proc_fleet_requests(wl)
    # ingress: the exact frame RemoteReplica.submit writes (+ newline)
    ingress = [len(Message(u, a, r).to_json().encode()) + 1
               for u, a, r in reqs]
    # reply: through the real hub so the seq envelope is priced too
    hub = procfleet._ReplyHub()
    for u, a, r in reqs:
        hub.publish(Message(u, a, summary=summary, reference=r))
    hub.close()
    reply = [len(frame.encode()) + 1 for frame in hub.stream(0)]
    assert len(reply) == len(ingress)
    payload = [len(u) + len(a) + len(summary) + len(r) for u, a, r in reqs]
    return {"ingress": ingress, "reply": reply, "payload": payload}


def test_proc_fleet_frame_bytes_under_ceilings(slo, proc_fleet_measured):
    """Codec creep gate: the wire frames the process transport actually
    produces (ingress submit + seq-stamped reply) stay under their
    committed per-request byte ceilings on the fleet mix."""
    sec, m = slo["process_fleet"], proc_fleet_measured
    per_req = [i + r for i, r in zip(m["ingress"], m["reply"])]
    assert max(m["ingress"]) <= sec["ingress_frame_bytes_max"], (
        f"ingress frame grew to {max(m['ingress'])} B (ceiling "
        f"{sec['ingress_frame_bytes_max']}) — the submit codec bloated "
        f"(see SERVE_SLO.json process_fleet._comment)")
    assert max(m["reply"]) <= sec["reply_frame_bytes_max"], (
        f"reply frame grew to {max(m['reply'])} B (ceiling "
        f"{sec['reply_frame_bytes_max']}) — the reply-hub envelope bloated")
    assert max(per_req) <= sec["wire_bytes_per_request_max"], (
        f"round-trip wire cost grew to {max(per_req)} B/request "
        f"(ceiling {sec['wire_bytes_per_request_max']})")


def test_proc_fleet_envelope_overhead_under_ceiling(slo,
                                                    proc_fleet_measured):
    """The JSON envelope (framing, escaping, the article echoed back in
    the reply) priced against the payload the caller actually asked to
    move — uuid + article + summary + reference counted once."""
    sec, m = slo["process_fleet"], proc_fleet_measured
    envelope = [i + r - p for i, r, p in
                zip(m["ingress"], m["reply"], m["payload"])]
    assert max(envelope) <= sec["envelope_overhead_bytes_max"], (
        f"wire envelope grew to {max(envelope)} B/request (ceiling "
        f"{sec['envelope_overhead_bytes_max']}) — schema creep or double "
        f"encoding in the socket transport")


def test_proc_fleet_scrape_bandwidth_under_ceiling(slo):
    """The supervisor's health scrape, priced at its real cadence: the
    REAL /healthz payload of a representative replica registry
    (breakers + heartbeats + serve gauges + ISSUE-17 incarnation
    identity), serialized once, multiplied by the scrapes/s the
    serve_scrape_interval_ms default implies."""
    from textsummarization_on_flink_tpu.obs import http as obs_http
    from textsummarization_on_flink_tpu.resilience.policy import \
        CircuitBreaker

    wl = slo["process_fleet"]["workload"]
    reg = Registry()
    reg.replica_id = "p0"
    for name in ("serve.admission", "serve.replica.p0", "io.source"):
        CircuitBreaker(threshold=2, name=name, registry=reg).allow()
    for comp in ("serve.engine", "serve.dispatch", "obs.flush"):
        obs_http.heartbeat(reg, comp)
    reg.gauge("serve/queue_depth").set(3)
    payload = obs_http.health(reg)
    # the incarnation identity the supervisor's readiness check keys on
    assert payload["pid"] == os.getpid()
    assert payload["replica_id"] == "p0"
    assert payload["start_time"] > 0
    scrape_bytes = len(json.dumps(payload).encode())
    scrapes_per_s = 1000.0 / wl["scrape_interval_ms"]
    kib_per_s = scrape_bytes * scrapes_per_s / 1024.0
    ceiling = slo["process_fleet"]["scrape_kib_per_replica_per_s_max"]
    assert kib_per_s <= ceiling, (
        f"health scrape costs {kib_per_s:.2f} KiB/s per replica "
        f"({scrape_bytes} B at {scrapes_per_s:.0f}/s; ceiling {ceiling}) "
        f"— the /healthz payload swelled past its scrape budget")


def test_proc_fleet_reply_ring_covers_inflight_capacity(slo):
    """At-least-once floor: a reply ring smaller than one replica's
    admissible in-flight set could trim frames a reconnecting
    supervisor never saw.  The hub capacity must dominate the
    serve_max_queue + slots bound the transport admits against."""
    from textsummarization_on_flink_tpu.serve import procfleet

    hps = HParams(mode="decode", batch_size=4, vocab_size=8,
                  max_enc_steps=8, max_dec_steps=4, min_dec_steps=1,
                  beam_size=2, max_oov_buckets=2,
                  serve_max_queue=256, serve_slots=8)
    capacity = hps.serve_max_queue + max(hps.serve_slots,
                                         hps.serve_max_batch, 1)
    hub = procfleet._ReplyHub()
    assert hub.capacity >= capacity, (
        f"reply ring ({hub.capacity}) smaller than one replica's "
        f"in-flight capacity ({capacity}) — a reconnect could replay "
        f"past live work")


# ---------------------------------------------------------------------------
# Hierarchical long-document summarization (ISSUE 19; SERVING.md
# "Hierarchical summarization") — the REAL HierarchicalSummarizer over a
# REAL continuous ServingServer with the front door armed, costed by the
# counting sim engine.  Fan-out makespan, the sequential baseline, and
# the append-path dedup are exact scheduling facts on the virtual clock.


def _hier_workload(slo):
    return {**slo["workload"], **slo["hierarchical"]["workload"]}


def _hier_doc(wl):
    """One doc exactly doc_chunks wide whose words are all DISTINCT
    (w0, w1, ...): distinct chunk content -> distinct article_key per
    chunk, so nothing coalesces WITHIN the first pass and the append
    pins measure the front door's dedup, not accidental twins.  The doc
    ends exactly on a chunk boundary (len = chunk + (n-1)*stride), so
    appending leaves every pre-append chunk byte-identical."""
    stride = wl["chunk_words"] - wl["overlap_words"]
    n_words = wl["chunk_words"] + (wl["doc_chunks"] - 1) * stride
    doc = " ".join(f"w{i}" for i in range(n_words))
    tail = " ".join(f"w{n_words + i}"
                    for i in range(wl["append_chunks"] * stride))
    return doc, tail


def _run_hier(slo, slots: int, append: bool):
    """Fan one document through a real continuous server with `slots`
    slots (slots=1 is the sequential baseline); optionally append and
    re-summarize on the warm server.  Returns the measured scheduling
    facts."""
    from textsummarization_on_flink_tpu.serve.hiersum import (
        DocumentSession,
        HierarchicalSummarizer,
    )

    wl = _hier_workload(slo)
    vocab = Vocab(words=WORDS)
    hps = HParams(
        mode="decode", batch_size=slots, vocab_size=vocab.size(),
        max_enc_steps=wl["chunk_words"], max_dec_steps=wl["long_steps"],
        beam_size=2, min_dec_steps=1, max_oov_buckets=4,
        serve_max_queue=256, serve_mode="continuous", serve_slots=slots,
        serve_refill_chunk=wl["chunk"], serve_coalesce=True,
        serve_cache_entries=wl["cache_entries"],
        hier_chunk_words=wl["chunk_words"],
        hier_overlap_words=wl["overlap_words"])
    doc, tail = _hier_doc(wl)
    out = {}
    with obs.use_registry(Registry()) as reg:
        sim = CountingSimEngine({**wl, "slots": slots})
        server = ServingServer(hps, vocab, decoder=_NullDecoder(),
                               engine=sim, registry=reg)
        hs = HierarchicalSummarizer(server, hps, registry=reg)
        sess = DocumentSession("doc", doc)
        marks = {}
        # enqueue the whole fan-out BEFORE the dispatch thread starts
        # (the committed discipline: slot assignment is pure FIFO)
        fut = hs.summarize("", session=sess)
        fut.add_done_callback(lambda f: marks.setdefault("fan", sim.vtime))
        server.start()
        res = fut.result(timeout=120)
        assert res.chunk_count == wl["doc_chunks"]
        out["fan_makespan"] = marks["fan"]
        out["fan_decodes"] = sim.pack_count
        if append:
            hits0 = reg.counter("serve/cache_hits_total").value
            packs0 = sim.pack_count
            t0 = sim.vtime  # idle ticks never step the engine
            sess.append(tail)
            fut2 = hs.summarize("", session=sess)
            fut2.add_done_callback(
                lambda f: marks.setdefault("app", sim.vtime))
            res2 = fut2.result(timeout=120)
            out["append_makespan"] = marks["app"] - t0
            out["append_hits"] = \
                reg.counter("serve/cache_hits_total").value - hits0
            out["append_decodes"] = sim.pack_count - packs0
            out["append_reused"] = res2.reused_chunks
            out["append_chunk_count"] = res2.chunk_count
            out["documents"] = \
                reg.counter("serve/hier_documents_total").value
            out["reduces"] = reg.counter("serve/hier_reduce_total").value
            out["partials"] = \
                reg.counter("serve/hier_partial_failures_total").value
        server.stop()
    return out


@pytest.fixture(scope="module")
def hier_measured(slo):
    wl = _hier_workload(slo)
    fan = _run_hier(slo, slots=wl["slots"], append=True)
    seq = _run_hier(slo, slots=1, append=False)
    return {"fan": fan, "seq": seq}


def test_hier_fanout_makespan_beats_sequential(slo, hier_measured):
    """The map-reduce win, gated: fanning the document's chunks over
    the slots must beat decoding them one after another by the
    committed ratio — and stay under the absolute ceiling."""
    sec = slo["hierarchical"]
    fan = hier_measured["fan"]["fan_makespan"]
    seq = hier_measured["seq"]["fan_makespan"]
    assert fan <= sec["fanout_makespan_virtual_ms_max"], (
        f"hier fan-out makespan {fan} vms (committed max "
        f"{sec['fanout_makespan_virtual_ms_max']}) — chunk scheduling "
        f"regressed")
    ratio = fan / seq
    assert ratio <= sec["fanout_makespan_ratio_max"], (
        f"hier fan-out makespan {fan} vms vs sequential {seq} (ratio "
        f"{ratio:.2f}, committed max {sec['fanout_makespan_ratio_max']}) "
        f"— the fan-out stopped buying parallelism")


def test_hier_append_dedups_by_construction(slo, hier_measured):
    """The append-path floor, pinned EXACTLY: re-summarizing after an
    append must cache-hit every pre-append chunk at submit and decode
    only the appended chunks + one reduce — chunk boundaries are a pure
    function of word index, so this is dedup by construction and any
    drift is a bug, not noise."""
    sec = slo["hierarchical"]
    wl = _hier_workload(slo)
    m = hier_measured["fan"]
    assert m["append_hits"] == sec["append_cache_hits_expected"], (
        f"append pass cache-hit {m['append_hits']} chunks (expected "
        f"exactly {sec['append_cache_hits_expected']}) — a boundary or "
        f"key drifted and the front door re-decoded unchanged content")
    assert m["append_decodes"] == sec["append_decodes_expected"], (
        f"append pass served {m['append_decodes']} decodes (expected "
        f"exactly {sec['append_decodes_expected']}: the appended chunks "
        f"+ one reduce)")
    assert m["append_reused"] == wl["doc_chunks"]
    assert m["append_chunk_count"] == \
        wl["doc_chunks"] + wl["append_chunks"]
    assert m["append_makespan"] <= sec["append_makespan_virtual_ms_max"]
    # bookkeeping: two documents, two reduces, zero partial failures
    assert m["documents"] == 2
    assert m["reduces"] == 2
    assert m["partials"] == 0
