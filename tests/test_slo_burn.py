"""The committed SLO burn-rate gate (ISSUE 15 acceptance;
SLO_POLICY.json at the repo root).

Same discipline as tests/test_serve_slo.py: the REAL serving stack
(ServingServer, RequestQueue, ContinuousBatcher, the obs/slo.py engine
installed by the server itself) driven single-threaded over VIRTUAL
time — the engine's clock is the gate's clock, so breach and recovery
are exact scheduling facts, no sleeps, no CI flake.

The committed scenario (SLO_POLICY.json "gate"): a victim tenant
trickles short articles while an attacker tenant submits long ones
whose end-to-end latency breaches the ``tenant_latency`` objective's
threshold.  Enforced here, in tier-1:

  * the attacker's fast-window burn rate drives its objective past the
    PAGE threshold within the fast window of the first breach;
  * the victim tenant's objective stays ``ok`` at every evaluation;
  * the page CLEARS after the breach ends (the multi-window rule: a
    clean fast window recovers the alert even while the slow window
    still remembers the breach);
  * the page transition dumps the flight-recorder ring
    (``flight_slo_burn.jsonl``) with every frame strictly pre-breach;
  * exemplar round-trip — the p99 bucket's exemplar trace_id
    reconstructs the offending request end-to-end through
    ``scripts/trace_summary.py --request`` from one events.jsonl.

Plus unit coverage of the engine itself: burn-rate arithmetic, the
multi-window min rule, declarative-objective validation, and the
hostile-tenant series bound.
"""

import json
import os
import sys

import pytest

from textsummarization_on_flink_tpu.serve.batcher import NoArena
from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.vocab import Vocab
from textsummarization_on_flink_tpu.decode.decoder import DecodedResult
from textsummarization_on_flink_tpu.obs import slo as slo_lib
from textsummarization_on_flink_tpu.obs.registry import Registry
from textsummarization_on_flink_tpu.serve.server import ServingServer

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
import trace_summary  # noqa: E402

POLICY_PATH = os.path.join(os.path.dirname(__file__), "..",
                           "SLO_POLICY.json")

WORDS = ["w"]


@pytest.fixture(scope="module")
def policy():
    with open(POLICY_PATH) as f:
        return json.load(f)


class _VClock:
    """The gate's virtual clock, in ms (seconds out of ``now`` — the
    server/engine clock unit)."""

    def __init__(self):
        self.ms = 0.0

    def now(self) -> float:
        return self.ms / 1000.0


class _NullDecoder:
    def maybe_reload_checkpoint(self, last):
        return last


class GateSimEngine(NoArena):
    """SlotDecodeEngine protocol over the SHARED virtual clock: each
    step() advances it by chunk * step_cost_ms and every active slot by
    ``chunk`` steps, so a long article's harvest lands ``long_steps *
    step_cost_ms`` virtual ms after its pack — the latency the
    ``tenant_latency`` objective classifies."""

    def __init__(self, wl, vclock):
        self.slots = wl["slots"]
        self.chunk = wl["chunk"]
        self._wl = wl
        self._vclock = vclock
        self._remaining = [0] * self.slots
        self._active = [False] * self.slots

    def pack(self, idx, example):
        assert not self._active[idx]
        short = example.enc_len <= self._wl["short_words"]
        self._active[idx] = True
        self._remaining[idx] = (self._wl["short_steps"] if short
                                else self._wl["long_steps"])

    def step(self):
        self._vclock.ms += self.chunk * self._wl["step_cost_ms"]
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
        return DecodedResult(
            uuid=example.uuid, article=example.original_article,
            decoded_words=["ok", "."], reference=example.reference,
            abstract_sents=[])

    def release(self, idx):
        self._active[idx] = False


def _alert_state(reg, key: str) -> float:
    """The slo/alert_state gauge for (tenant_latency, key): 0 ok,
    1 warn, 2 page."""
    return reg.gauge("slo/alert_state").labels(
        objective="tenant_latency", key=key).value


@pytest.fixture(scope="module")
def gate_run(policy, tmp_path_factory):
    """ONE deterministic run of the committed breach-and-recover
    scenario; every gate test below reads its facts."""
    wl = policy["gate"]
    tmp = tmp_path_factory.mktemp("slo_gate")
    events_dir = str(tmp / "events")
    vocab = Vocab(words=WORDS)
    vclock = _VClock()
    hps = HParams(
        mode="decode", batch_size=wl["slots"], vocab_size=vocab.size(),
        max_enc_steps=wl["long_words"], max_dec_steps=wl["long_steps"],
        beam_size=2, min_dec_steps=1, max_oov_buckets=4,
        serve_max_queue=wl["queue"],
        serve_mode="continuous", serve_slots=wl["slots"],
        serve_refill_chunk=wl["chunk"],
        serve_fair_weights=wl["fair_weights"],
        log_root=str(tmp), exp_name="slo_gate")
    reg = Registry()
    sink = obs.install_event_sink(events_dir, flush_secs=0.05, reg=reg)
    sim = GateSimEngine(wl, vclock)
    server = ServingServer(hps, vocab, decoder=_NullDecoder(),
                           engine=sim, registry=reg, clock=vclock.now)
    assert reg.slo is not None, \
        "ServingServer must install the committed SLO engine"
    futures = []
    page_at_s = None
    ticks_at_page = None
    victim_states = []
    attacker_trajectory = []  # (virtual s, attacker state) per round
    rounds = wl["rounds_breach"] + wl["rounds_recover"]
    for rnd in range(rounds):
        futures.append(server.submit(
            " ".join(WORDS * wl["short_words"]), uuid=f"v{rnd}",
            tenant="victim"))
        n_words = (wl["long_words"] if rnd < wl["rounds_breach"]
                   else wl["short_words"])
        futures.append(server.submit(
            " ".join(WORDS * n_words), uuid=f"a{rnd}",
            tenant="attacker"))
        server.tick_once(poll=0.0)
        a_state = _alert_state(reg, "attacker")
        victim_states.append(_alert_state(reg, "victim"))
        attacker_trajectory.append((vclock.now(), a_state))
        if page_at_s is None and a_state == 2:
            page_at_s = vclock.now()
            ticks_at_page = rnd + 1
    # drain: every admitted request resolves exactly once
    for _ in range(100):
        if all(f.done() for f in futures):
            break
        server.tick_once(poll=0.0)
    results = [f.result(timeout=0) for f in futures]
    server.stop()
    sink.close()
    events_path = None
    for root, _, names in os.walk(events_dir):
        if "events.jsonl" in names:
            events_path = os.path.join(root, "events.jsonl")
    assert events_path is not None
    return {
        "wl": wl, "reg": reg, "results": results,
        "page_at_s": page_at_s, "ticks_at_page": ticks_at_page,
        "victim_states": victim_states,
        "attacker_trajectory": attacker_trajectory,
        "final_attacker_state": _alert_state(reg, "attacker"),
        "dump_dir": str(tmp / "slo_gate"),
        "events_path": events_path,
    }


def test_attacker_breach_pages_within_fast_window(gate_run):
    """The committed paging promise: a sustained latency breach by one
    tenant drives ITS fast-window burn rate past the page threshold
    within the fast window of the breach starting (t=0 virtual)."""
    wl = gate_run["wl"]
    assert gate_run["page_at_s"] is not None, \
        "attacker latency breach never paged"
    assert gate_run["page_at_s"] <= wl["page_within_secs"], (
        f"page came at +{gate_run['page_at_s']:.0f} virtual s (committed "
        f"within {wl['page_within_secs']:.0f}) — the fast window is not "
        f"doing its job")
    burn = gate_run["reg"].gauge("slo/burn_rate_fast").labels(
        objective="tenant_latency", key="attacker")
    # the gauge family is live: SOME evaluation pushed the attacker's
    # fast burn past the page threshold (it may have recovered since)
    assert any(s == 2 for _, s in gate_run["attacker_trajectory"])
    assert burn is not None


def test_victim_objective_stays_ok_throughout(gate_run):
    """Tenant isolation, telemetry edition: the attacker's breach is
    attributed to the attacker — the victim's objective never leaves
    ``ok`` at any evaluation of the run."""
    assert all(s == 0 for s in gate_run["victim_states"]), (
        f"victim alert states left ok: "
        f"{sorted(set(gate_run['victim_states']))}")


def test_alert_recovers_after_breach_ends(gate_run):
    """Symmetric recovery (the multi-window min rule): once the
    attacker's traffic goes clean and the fast window slides past the
    breach, the page clears — even though the slow window still
    remembers it."""
    assert gate_run["final_attacker_state"] == 0, (
        "attacker objective still not ok after "
        f"{gate_run['wl']['rounds_recover']} clean rounds")
    # and the recovery happened AFTER a real page (not vacuous)
    states = [s for _, s in gate_run["attacker_trajectory"]]
    assert states.index(2) < len(states) - 1 and states[-1] == 0


def test_slo_burn_flight_dump_ring_strictly_pre_breach(gate_run):
    """The page transition dumps the flight ring exactly like
    ``train_nan``: ``flight_slo_burn.jsonl`` lands next to the decode
    output, its header names the paged (objective, key), and every
    ring frame precedes the breach evaluation (ticks <= the round the
    page fired on)."""
    path = os.path.join(gate_run["dump_dir"], "flight_slo_burn.jsonl")
    assert os.path.exists(path), (
        f"no slo_burn flight dump in {gate_run['dump_dir']}")
    with open(path) as f:
        recs = [json.loads(ln) for ln in f if ln.strip()]
    header, frames = recs[0], recs[1:]
    assert header["kind"] == "flight" and header["reason"] == "slo_burn"
    assert header["context"]["objective"] == "tenant_latency"
    assert header["context"]["key"] == "attacker"
    assert header["context"]["burn_fast"] >= 8.0  # the committed page
    assert frames, "empty ring dumped"
    ticks = [fr["tick"] for fr in frames if "tick" in fr]
    assert ticks and max(ticks) <= gate_run["ticks_at_page"], (
        f"ring frames past the breach: max tick {max(ticks)} vs page at "
        f"tick {gate_run['ticks_at_page']}")


def test_exemplar_round_trip_through_trace_summary(gate_run):
    """ISSUE 15 acceptance, exemplar leg: the e2e histogram's p99
    bucket carries a trace_id exemplar, and that trace_id — pasted
    straight into ``trace_summary.py --request`` — reconstructs the
    offending request's full timeline from the run's one
    events.jsonl."""
    reg = gate_run["reg"]
    h = reg.get("serve/e2e_latency_seconds")
    # the histogram runs on wall time (the engine is simulated, the
    # scheduler is real); the exemplar contract is about the JUMP, not
    # the magnitude: the bucket holding the p99 names a trace_id
    p99 = h.percentile(99)
    fat = next(e for e in h.exemplars()
               if e["le"] == "+Inf" or float(e["le"]) >= p99)
    tl = trace_summary.request_timeline(
        [gate_run["events_path"]], fat["trace_id"])
    assert tl["events"], f"exemplar {fat['trace_id']} matched no events"
    assert tl["trace_id"] == fat["trace_id"]
    # ...and the trace resolves back to one real request of the run
    assert tl["uuid"] and tl["uuid"][0] in ("a", "v"), tl["uuid"]
    stages = {e["event"] for e in tl["events"]}
    assert {"enqueue", "slot", "finish", "resolve"} <= stages, stages
    assert tl["phases"].get("total_ms") is not None


def test_every_future_resolved_exactly_once(gate_run):
    uuids = [r.uuid for r in gate_run["results"]]
    assert len(uuids) == len(set(uuids)) == 2 * (
        gate_run["wl"]["rounds_breach"] + gate_run["wl"]["rounds_recover"])


# --------------------------------------------------------------------------
# engine unit coverage
# --------------------------------------------------------------------------

def _mini_policy(**over):
    pol = {
        "windows": {"fast_secs": 10.0, "slow_secs": 100.0,
                    "bucket_secs": 1.0},
        "thresholds": {"warn": 2.0, "page": 10.0},
        "objectives": [{"name": "lat", "signal": "latency",
                        "by": "tenant", "latency_threshold_ms": 1000.0,
                        "target": 0.9}],
    }
    pol.update(over)
    return pol


class TestSloEngine:
    def test_burn_rate_arithmetic_exact(self):
        t = [100.0]
        eng = slo_lib.SloEngine(_mini_policy(), Registry(),
                                clock=lambda: t[0])
        for _ in range(8):
            eng.record("a", "beam", 0.5)   # good
        for _ in range(2):
            eng.record("a", "beam", 2.0)   # bad: over the 1s threshold
        rows = eng.evaluate()
        (row,) = rows
        # frac_bad 0.2 / budget 0.1 -> burn 2.0, exactly
        assert row["burn_fast"] == 2.0 and row["burn_slow"] == 2.0
        assert row["state"] == "warn"
        assert row["events_fast"] == 10

    def test_multi_window_min_rule(self):
        """Bad events older than the fast window cannot page on their
        own: effective burn is min(fast, slow)."""
        t = [0.0]
        eng = slo_lib.SloEngine(_mini_policy(), Registry(),
                                clock=lambda: t[0])
        for _ in range(10):
            eng.record("a", "beam", 5.0)  # all bad -> burn 10 both
        (row,) = eng.evaluate()
        assert row["state"] == "page"
        # slide past the fast window with clean traffic
        t[0] = 50.0
        for _ in range(10):
            eng.record("a", "beam", 0.1)
        (row,) = eng.evaluate()
        assert row["burn_fast"] == 0.0
        assert row["burn_slow"] > 0.0  # the slow window still remembers
        assert row["state"] == "ok"

    def test_error_signal_objective(self):
        pol = _mini_policy(objectives=[{
            "name": "errs", "signal": "error", "by": "tier",
            "target": 0.5}])
        t = [0.0]
        eng = slo_lib.SloEngine(pol, Registry(), clock=lambda: t[0])
        eng.record("a", "beam", 0.1, error=True)
        eng.record("a", "beam", 0.1, error=False)
        (row,) = eng.evaluate()
        assert row["key"] == "beam" and row["burn_fast"] == 1.0

    def test_objective_validation(self):
        with pytest.raises(ValueError):
            slo_lib.Objective({"name": "x", "signal": "nope"})
        with pytest.raises(ValueError):
            slo_lib.Objective({"name": "x", "by": "region"})
        with pytest.raises(ValueError):
            slo_lib.Objective({"name": "x", "target": 1.5})
        with pytest.raises(ValueError):
            slo_lib.Objective({"name": "x", "signal": "latency",
                               "latency_threshold_ms": 0})

    def test_hostile_tenant_series_bound(self, monkeypatch):
        monkeypatch.setattr(slo_lib, "MAX_SLO_SERIES", 8)
        reg = Registry()
        t = [0.0]
        eng = slo_lib.SloEngine(_mini_policy(), reg, clock=lambda: t[0])
        for i in range(100):
            eng.record(f"hostile-{i}", "beam", 0.1)
        assert len(eng._series) == 8
        assert reg.counter("slo/series_evictions_total").value == 92

    def test_alerts_payload_without_engine(self):
        payload = slo_lib.alerts_payload(Registry())
        assert payload == {"status": "ok", "installed": False,
                           "objectives": []}

    def test_install_with_missing_policy_is_noop(self, monkeypatch):
        monkeypatch.setenv(slo_lib.ENV_POLICY, "/nonexistent/slo.json")
        reg = Registry()
        assert slo_lib.install_slo_engine(reg) is None
        assert reg.slo is None

    def test_slo_label_caps_match_engine_series_bound(self):
        """The slo/* metrics must hold one labeled child per live
        engine series — a cap below MAX_SLO_SERIES would LRU-thrash the
        gauge children every evaluate() and drop paging series from
        the scraped exposition."""
        reg = Registry()
        slo_lib.SloEngine(_mini_policy(), reg)
        for name in ("slo/burn_rate_fast", "slo/burn_rate_slow",
                     "slo/alert_state", "slo/good_total",
                     "slo/bad_total"):
            assert reg.get(name)._max_label_sets >= \
                slo_lib.MAX_SLO_SERIES, name

    def test_track_request_helper_counts_once_and_classifies(self):
        """The shared ingress helper (serve/queue.py): one labeled
        requests_total inc, one SLO record on the future's exactly-once
        resolution, latency on the caller's clock."""
        from textsummarization_on_flink_tpu.serve.queue import (
            ServeFuture,
            track_request,
        )

        reg = Registry()
        eng = slo_lib.install_slo_engine(reg, policy=_mini_policy())
        t = [0.0]
        fut = ServeFuture("u1", registry=reg)
        track_request(reg, lambda: t[0], fut, "", "beam")
        assert reg.counter("serve/requests_total").labels(
            tenant="default", tier="beam").value == 1
        t[0] = 5.0  # resolves 5 virtual s later: over the 1s threshold
        fut._resolve("ok")
        (row,) = eng.evaluate()
        assert row["key"] == "default" and row["events_fast"] == 1
        assert row["burn_fast"] == 10.0  # frac_bad 1.0 / budget 0.1

    def test_committed_policy_loads(self, policy):
        """SLO_POLICY.json itself parses into a working engine."""
        eng = slo_lib.SloEngine(policy, Registry())
        assert {o.name for o in eng.objectives} == {
            "tenant_latency", "tier_latency", "tier_errors"}
        assert eng.page == policy["thresholds"]["page"]
