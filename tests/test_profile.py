"""Performance attribution gate (ISSUE 16 acceptance;
OBSERVABILITY.md "Performance attribution").

Four committed behaviors of obs/profile.py, enforced in tier-1:

  * **phase accounting** — driven over a scripted virtual clock, the
    phase ledger's durations sum EXACTLY to the wall bracket
    (coverage == 1.0), and on the REAL continuous serving stack (sim
    engine over the shared virtual clock, same discipline as
    tests/test_slo_burn.py) the ledger attributes >= 95% of the
    admit -> resolve window;
  * **compile storm** — one compile past a site's committed budget
    dumps the flight ring (``flight_compile_storm.jsonl``) and lands
    on the cached /alerts state;
  * **divergence sentinel** — 10x-the-factor wall inflation on a
    priced shape dumps ``flight_perf_divergence.jsonl``; dispatches at
    the warm baseline stay silent;
  * **null path** — a dark registry gets the shared NULL_PROFILER and
    its per-dispatch record calls allocate nothing (pinned via
    ``sys.getallocatedblocks``).

Plus unit coverage of compiled_call (one shared jit-cache diff,
hit/miss counters + ledger keys) and the /profile HTTP route.
"""

import gc
import json
import sys
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from textsummarization_on_flink_tpu.serve.batcher import NoArena
from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.vocab import Vocab
from textsummarization_on_flink_tpu.decode.decoder import DecodedResult
from textsummarization_on_flink_tpu.obs import flightrec
from textsummarization_on_flink_tpu.obs import profile as profile_lib
from textsummarization_on_flink_tpu.obs.registry import Registry


class ScriptClock:
    """A hand-advanced clock: time moves only when the test says so,
    making phase durations exact arithmetic facts."""

    def __init__(self):
        self.t = 0.0

    def now(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class TestPhaseLedger:
    def test_phases_sum_to_wall_exactly_in_virtual_time(self):
        reg = Registry()
        clock = ScriptClock()
        prof = profile_lib.install_profiler(reg, clock=clock.now)
        w0 = prof.start()
        for phase, cost, trace in [("serve/prefill", 0.010, "tr-1"),
                                   ("serve/pack", 0.002, None),
                                   ("serve/dispatch", 0.030, None),
                                   ("serve/harvest", 0.003, None)]:
            t0 = prof.start()
            clock.advance(cost)
            dt = prof.end(phase, t0, trace_id=trace)
            assert dt == pytest.approx(cost)
        wall = prof.end_wall("serve/tick", w0)
        assert wall == pytest.approx(0.045)
        stats = prof.phase_stats()
        assert set(stats) == {"serve/prefill", "serve/pack",
                              "serve/dispatch", "serve/harvest"}
        assert stats["serve/dispatch"] == (1, pytest.approx(0.030),
                                           pytest.approx(0.030))
        # every advanced tick is attributed to a named phase
        assert prof.coverage() == pytest.approx(1.0)
        assert reg.gauge("profile/phase_coverage_ratio").value == \
            pytest.approx(1.0)
        # the ring keeps the trace exemplar for the slowest-dispatch
        # table
        ring = prof.recent_phases()
        assert [r[1] for r in ring] == ["serve/prefill", "serve/pack",
                                        "serve/dispatch", "serve/harvest"]
        assert ring[0][3] == "tr-1"

    def test_unattributed_time_sinks_coverage(self):
        """Clock advance OUTSIDE any phase bracket shows up as missing
        coverage — the accounting check this ledger exists for."""
        prof = profile_lib.install_profiler(Registry(),
                                            clock=(c := ScriptClock()).now)
        w0 = prof.start()
        t0 = prof.start()
        c.advance(0.040)
        prof.end("serve/dispatch", t0)
        c.advance(0.060)  # unattributed: no phase bracket open
        prof.end_wall("serve/tick", w0)
        assert prof.coverage() == pytest.approx(0.4)

    def test_recent_ring_is_bounded(self):
        prof = profile_lib.install_profiler(Registry(),
                                            clock=ScriptClock().now)
        for _ in range(profile_lib.RECENT_PHASES_CAP + 64):
            prof.end("serve/dispatch", prof.start())
        assert len(prof.recent_phases()) == profile_lib.RECENT_PHASES_CAP

    def test_payload_carries_slowest_dispatches_and_notes(self):
        reg = Registry()
        clock = ScriptClock()
        prof = profile_lib.install_profiler(reg, clock=clock.now)
        for dur, trace in [(0.001, "fast"), (0.500, "slow"),
                           (0.002, None)]:
            t0 = prof.start()
            clock.advance(dur)
            prof.end("serve/dispatch", t0, trace_id=trace)
        prof.note("profiler_capture", dir="/tmp/x", start_step=2)
        payload = profile_lib.profile_payload(reg)
        assert payload["installed"]
        slowest = payload["slowest"]
        assert slowest[0]["trace_id"] == "slow"
        assert slowest[0]["dur_s"] == pytest.approx(0.5)
        assert payload["notes"][0]["note"] == "profiler_capture"
        assert payload["notes"][0]["dir"] == "/tmp/x"


class TestCompileLedger:
    def test_compiled_call_diffs_the_jit_cache(self):
        reg = Registry()
        fn = jax.jit(lambda x: x * 2.0)
        out = profile_lib.compiled_call(reg, "decode/step_slots_jit", fn,
                                        jnp.ones((2,)), key="chunk2")
        assert float(out[0]) == 2.0
        profile_lib.compiled_call(reg, "decode/step_slots_jit", fn,
                                  jnp.ones((2,)), key="chunk2")
        site = reg.profile.compile_stats()["decode/step_slots_jit"]
        assert site["compiles"] == 1
        assert site["hits"] == 1
        assert site["keys"] == ["chunk2"]
        assert reg.counter(
            "decode/compile_cache_misses_total").value == 1.0
        assert reg.counter(
            "decode/compile_cache_hits_total").value == 1.0

    def test_compiled_call_books_the_phase_too(self):
        """One timing, both ledgers: `phase=` lands the measured wall
        in the phase ledger alongside the compile event."""
        reg = Registry()
        fn = jax.jit(lambda x: x + 1.0)
        profile_lib.compiled_call(reg, "decode/beam_search_jit", fn,
                                  jnp.ones((2,)), key="scan",
                                  phase="decode/beam_search")
        stats = reg.profile.phase_stats()
        assert stats["decode/beam_search"][0] == 1

    def test_budget_reregistration_keeps_the_max(self):
        prof = profile_lib.install_profiler(Registry())
        prof.set_compile_budget("decode/prefill_jit", 3)
        prof.set_compile_budget("decode/prefill_jit", 2)
        prof.record_compile("decode/prefill_jit", 64, 0.1)
        assert prof.compile_stats()["decode/prefill_jit"]["budget"] == 3

    def test_compile_past_budget_dumps_the_flight_ring(self, tmp_path):
        reg = Registry()
        assert flightrec.install_flight_recorder(
            reg, str(tmp_path)) is not None
        prof = profile_lib.install_profiler(reg)
        prof.set_compile_budget("decode/step_slots_jit", 1)
        prof.record_compile("decode/step_slots_jit", "chunk2", 0.5)
        # within budget: no storm, nothing cached for /alerts
        assert profile_lib.profile_alerts(reg)["compile_storm"] is None
        assert not (tmp_path / "flight_compile_storm.jsonl").exists()
        # the second compile of a budget-1 site IS the storm
        prof.record_compile("decode/step_slots_jit", "chunk4", 0.4)
        dump = tmp_path / "flight_compile_storm.jsonl"
        assert dump.exists(), list(tmp_path.iterdir())
        storm = profile_lib.profile_alerts(reg)["compile_storm"]
        assert storm["site"] == "decode/step_slots_jit"
        assert storm["compiles"] == 2 and storm["budget"] == 1
        assert reg.counter("profile/compile_storms_total").value == 1.0
        # the warm set counts every compile across sites
        assert prof.warm_set_size() == 2
        # the payload serves the same cached storm (scrapes never
        # re-trigger dumps)
        assert profile_lib.profile_payload(
            reg)["compile_ledger"]["storm"]["key"] == "chunk4"


class TestDivergenceSentinel:
    def test_inflated_wall_dumps_silent_at_baseline(self, tmp_path):
        reg = Registry()
        assert flightrec.install_flight_recorder(
            reg, str(tmp_path)) is not None
        prof = profile_lib.install_profiler(reg, divergence_factor=5.0)
        prof.prime_cost("serve/dispatch", "slot_chunk8",
                        flops=1e9, bytes_=1e6)
        # warmup window establishes the baseline (best of the first N)
        for _ in range(profile_lib.BASELINE_SAMPLES):
            prof.observe_dispatch("serve/dispatch", "slot_chunk8", 0.010)
        # judged dispatches at the warm baseline: silent
        prof.observe_dispatch("serve/dispatch", "slot_chunk8", 0.011)
        assert not (tmp_path / "flight_perf_divergence.jsonl").exists()
        assert profile_lib.profile_alerts(reg)["divergence"] == []
        assert reg.counter("profile/divergence_dumps_total").value == 0.0
        # 50x the baseline wall = 10x past the committed 5x factor
        prof.observe_dispatch("serve/dispatch", "slot_chunk8", 0.500,
                              trace_id="tr-div")
        assert (tmp_path / "flight_perf_divergence.jsonl").exists(), \
            list(tmp_path.iterdir())
        assert reg.counter("profile/divergence_dumps_total").value == 1.0
        diverged = profile_lib.profile_alerts(reg)["divergence"]
        assert len(diverged) == 1
        assert diverged[0]["site"] == "serve/dispatch"
        assert diverged[0]["drift"] == pytest.approx(50.0, rel=0.1)
        # achieved-throughput gauges track the LAST dispatch
        assert reg.gauge("profile/achieved_bytes_per_second").labels(
            site="serve/dispatch").value == pytest.approx(1e6 / 0.5)
        assert reg.gauge("profile/achieved_flops_per_second").labels(
            site="serve/dispatch").value == pytest.approx(1e9 / 0.5)

    def test_unpriced_shape_stays_quiet(self):
        reg = Registry()
        prof = profile_lib.install_profiler(reg)
        prof.observe_dispatch("serve/dispatch", "never_priced", 1.0)
        assert profile_lib.profile_payload(reg)["divergence"] == []

    def test_divergence_factor_is_validated(self):
        with pytest.raises(ValueError, match="profile_divergence_factor"):
            HParams(profile_divergence_factor=1.0).validate()


class TestNullPath:
    def test_dark_registry_gets_the_shared_null_profiler(self):
        assert profile_lib.profiler_for(None) is profile_lib.NULL_PROFILER
        assert profile_lib.profiler_for(
            Registry(enabled=False)) is profile_lib.NULL_PROFILER
        assert profile_lib.install_profiler(
            Registry(enabled=False)) is profile_lib.NULL_PROFILER

    def test_null_payload_shape(self):
        payload = profile_lib.profile_payload(None)
        assert payload["installed"] is False
        assert payload["compile_ledger"]["warm_set"] == 0
        alerts = profile_lib.profile_alerts(Registry(enabled=False))
        assert alerts == {"installed": False, "compile_storm": None,
                          "divergence": []}

    def test_null_path_adds_no_per_dispatch_allocation(self):
        """The obs=False pin: a record-path burst through the null
        profiler must not grow the allocated-block count — constants
        out, nothing retained."""
        prof = profile_lib.profiler_for(Registry(enabled=False))
        assert prof is profile_lib.NULL_PROFILER

        def burst(n):
            for _ in range(n):
                t0 = prof.start()
                prof.end("serve/dispatch", t0)
                prof.observe_dispatch("serve/dispatch", "k", 0.001)
                prof.record_hit("decode/step_slots_jit")
                prof.record_compile("decode/step_slots_jit", "k", 0.0)

        burst(64)  # warm any lazy interpreter state first
        gc.collect()
        before = sys.getallocatedblocks()
        burst(512)
        delta = sys.getallocatedblocks() - before
        assert delta <= 16, (
            f"null profiler leaked {delta} blocks over 512 dispatches")


# ---- the real-stack virtual-time gate ---------------------------------

class _VClock:
    def __init__(self):
        self.ms = 0.0

    def now(self) -> float:
        return self.ms / 1000.0


class _NullDecoder:
    def maybe_reload_checkpoint(self, last):
        return last


class _SimEngine(NoArena):
    """SlotDecodeEngine protocol over the shared virtual clock: pack
    and step are the only operations that cost virtual time, and both
    run inside profiler phase brackets — so whatever fraction the
    ledger fails to attribute is a REAL accounting hole, not jitter."""

    def __init__(self, vclock, slots, chunk, steps_per_req,
                 step_cost_ms, pack_cost_ms):
        self.slots = slots
        self.chunk = chunk
        self._vclock = vclock
        self._steps = steps_per_req
        self._step_cost_ms = step_cost_ms
        self._pack_cost_ms = pack_cost_ms
        self._remaining = [0] * slots
        self._active = [False] * slots

    def pack(self, idx, example):
        assert not self._active[idx]
        self._vclock.ms += self._pack_cost_ms
        self._active[idx] = True
        self._remaining[idx] = self._steps

    def step(self):
        self._vclock.ms += self.chunk * self._step_cost_ms
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


class TestServedRequestCoverage:
    def test_phase_ledger_accounts_admit_to_resolve(self, tmp_path):
        """The acceptance gate: on the real continuous serving stack
        over virtual time, the phase ledger attributes >= 95% of the
        submit -> all-resolved wall window (here it is exact: every
        virtual tick spent belongs to a named phase)."""
        from textsummarization_on_flink_tpu.serve.server import (
            ServingServer,
        )
        vocab = Vocab(words=["w"])
        vclock = _VClock()
        hps = HParams(
            mode="decode", batch_size=2, vocab_size=vocab.size(),
            max_enc_steps=8, max_dec_steps=8, beam_size=2,
            min_dec_steps=1, max_oov_buckets=4, serve_max_queue=16,
            serve_mode="continuous", serve_slots=2,
            serve_refill_chunk=4, log_root=str(tmp_path),
            exp_name="profile_gate")
        reg = Registry()
        sim = _SimEngine(vclock, slots=2, chunk=4, steps_per_req=8,
                         step_cost_ms=5.0, pack_cost_ms=1.0)
        server = ServingServer(hps, vocab, decoder=_NullDecoder(),
                               engine=sim, registry=reg,
                               clock=vclock.now)
        # the server installed the profiler on ITS clock — virtual
        # time in this gate
        assert reg.profile is not None
        t_submit = vclock.now()
        futures = [server.submit("w w w", uuid=f"p{i}")
                   for i in range(4)]
        for _ in range(64):
            if all(f.done() for f in futures):
                break
            server.tick_once(poll=0.0)
        results = [f.result(timeout=0) for f in futures]
        window = vclock.now() - t_submit
        server.stop()
        assert len(results) == 4
        assert all(r.decoded_words == ["ok", "."] for r in results)
        assert window > 0.0
        stats = reg.profile.phase_stats()
        assert {"serve/pack", "serve/dispatch",
                "serve/harvest", "serve/evict"} <= set(stats)
        attributed = sum(total for _, total, _ in stats.values())
        assert attributed >= 0.95 * window, (
            f"phase ledger attributed {attributed:.4f}s of a "
            f"{window:.4f}s admit->resolve window")
        # the wall bracket saw every busy tick, and the committed
        # coverage gauge agrees with the accounting
        assert reg.profile.coverage() >= 0.95
        payload = profile_lib.profile_payload(reg)
        assert [w["wall"] for w in payload["walls"]] == ["serve/tick"]
        # the sim engine never compiles: an empty compile ledger, no
        # storm
        assert payload["compile_ledger"]["warm_set"] == 0
        assert payload["compile_ledger"]["storm"] is None


class TestProfileRoute:
    def _get(self, port, route):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{route}", timeout=10) as resp:
            return resp.status, json.loads(resp.read())

    def test_profile_route_serves_the_payload(self):
        reg = Registry()
        clock = ScriptClock()
        prof = profile_lib.install_profiler(reg, clock=clock.now)
        t0 = prof.start()
        clock.advance(0.010)
        prof.end("serve/dispatch", t0)
        srv = obs.serve_http(0, reg)
        try:
            status, payload = self._get(srv.port, "/profile")
            assert status == 200
            assert payload["installed"]
            assert [p["phase"] for p in payload["phases"]] == \
                ["serve/dispatch"]
            # the profiler's cached state rides /alerts too
            status, alerts = self._get(srv.port, "/alerts")
            assert status == 200
            assert alerts["profile"]["installed"]
            assert alerts["profile"]["compile_storm"] is None
        finally:
            srv.close()

    def test_profile_route_quiet_when_uninstalled(self):
        reg = Registry()
        srv = obs.serve_http(0, reg)
        try:
            status, payload = self._get(srv.port, "/profile")
            assert status == 200
            assert payload["installed"] is False
            assert payload["phases"] == []
        finally:
            srv.close()


# ---- the one bracket: Profiler.phase / Profiler.wall -------------------

class _RecordingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: same constructor and
    context protocol, keeps what was opened and closed."""

    log = []

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        self.log.append(("enter", self.name, self.attrs))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.attrs))


class TestPhaseBracket:
    def _prof(self, monkeypatch):
        reg = Registry()
        clock = ScriptClock()
        prof = profile_lib.install_profiler(reg, clock=clock.now)
        _RecordingAnnotation.log = []
        monkeypatch.setattr(profile_lib, "_trace_annotation",
                            lambda: _RecordingAnnotation)
        return reg, clock, prof

    def test_books_ledger_span_and_annotation_once_each_and_nests(
            self, monkeypatch):
        reg, clock, prof = self._prof(monkeypatch)
        with prof.wall("serve/tick"):
            with prof.phase("serve/harvest", trace_id="tr-9") as outer:
                clock.advance(0.001)
                with prof.phase("serve/harvest/unpack", slot=3) as inner:
                    clock.advance(0.002)
        assert inner.dt == pytest.approx(0.002)
        assert outer.dt == pytest.approx(0.003)
        # ledger: both booked once; the child is in the table and out of
        # coverage (its parent already accounts for the interval)
        stats = prof.phase_stats()
        assert stats["serve/harvest"][0] == 1
        assert stats["serve/harvest/unpack"] == (
            1, pytest.approx(0.002), pytest.approx(0.002))
        assert prof.coverage() == pytest.approx(1.0)
        assert reg.histogram("profile/phase_seconds").labels(
            phase="serve/harvest/unpack").count == 1
        # the ring (the harness names idle gaps from it) keeps top-level
        # phases only, with the exemplar
        assert [(r[1], r[3]) for r in prof.recent_phases()] == [
            ("serve/harvest", "tr-9")]
        # spans: one each, nested, attrs on the child
        spans = {s.name: s for s in obs.tracer_for(reg).finished()}
        assert set(spans) == {"serve/harvest", "serve/harvest/unpack"}
        assert spans["serve/harvest/unpack"].parent == "serve/harvest"
        assert spans["serve/harvest/unpack"].attrs == {"slot": 3}
        assert spans["serve/harvest"].parent is None
        # annotations: wall ⊃ phase ⊃ child, each opened and closed once
        assert [(k, n) for k, n, _ in _RecordingAnnotation.log] == [
            ("enter", "serve/tick"), ("enter", "serve/harvest"),
            ("enter", "serve/harvest/unpack"),
            ("exit", "serve/harvest/unpack"), ("exit", "serve/harvest"),
            ("exit", "serve/tick")]
        assert _RecordingAnnotation.log[2][2] == {"slot": 3}

    def test_flat_end_inside_a_phase_is_a_child_too(self, monkeypatch):
        """compiled_call(phase=...) books through start()/end(): inside
        a phase() it must not count twice toward coverage."""
        _, clock, prof = self._prof(monkeypatch)
        with prof.wall("serve/tick"):
            with prof.phase("serve/dispatch"):
                t0 = prof.start()
                clock.advance(0.004)
                prof.end("decode/beam_search", t0)
        assert prof.phase_stats()["decode/beam_search"][0] == 1
        assert prof.coverage() == pytest.approx(1.0)
        assert [r[1] for r in prof.recent_phases()] == ["serve/dispatch"]

    def test_a_raising_body_still_closes_everything(self, monkeypatch):
        reg, clock, prof = self._prof(monkeypatch)
        with pytest.raises(RuntimeError):
            with prof.phase("serve/dispatch"):
                clock.advance(0.005)
                raise RuntimeError("boom")
        assert prof.phase_stats()["serve/dispatch"][1] == \
            pytest.approx(0.005)
        assert [k for k, _, _ in _RecordingAnnotation.log] == [
            "enter", "exit"]
        assert len(obs.tracer_for(reg).finished()) == 1
        # the depth unwound: the next phase is top-level again
        with prof.phase("serve/pack"):
            pass
        assert [r[1] for r in prof.recent_phases()] == [
            "serve/dispatch", "serve/pack"]

    def test_cancelled_wall_books_nothing(self, monkeypatch):
        _, clock, prof = self._prof(monkeypatch)
        with prof.wall("serve/tick") as wall:
            clock.advance(0.050)  # an idle tick's queue poll
            wall.cancel()
        assert profile_lib.profile_payload(prof._reg)["walls"] == []
        with prof.wall("serve/tick"):
            clock.advance(0.010)
        walls = profile_lib.profile_payload(prof._reg)["walls"]
        assert [(w["wall"], w["count"]) for w in walls] == [
            ("serve/tick", 1)]

    def test_phases_land_in_the_profilers_host_plane(self, tmp_path):
        """With jax loaded the bracket IS a TraceAnnotation: a capture
        holds parent and child on one thread's line, the child inside
        the parent, on the profiler's clock."""
        from jax.profiler import ProfileData
        import glob

        assert profile_lib._trace_annotation() is \
            jax.profiler.TraceAnnotation
        prof = profile_lib.install_profiler(Registry())
        jax.profiler.start_trace(str(tmp_path))
        with prof.phase("serve/harvest"):
            with prof.phase("serve/harvest/unpack", slot=1):
                time.sleep(0.001)
        jax.profiler.stop_trace()
        path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
        found = []
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                evs = {e.name: e for e in line.events
                       if e.name.startswith("serve/harvest")}
                if evs:
                    found.append(evs)
        assert len(found) == 1  # one thread, one line
        outer, inner = (found[0]["serve/harvest"],
                        found[0]["serve/harvest/unpack"])
        assert outer.start_ns <= inner.start_ns
        assert (inner.start_ns + inner.duration_ns
                <= outer.start_ns + outer.duration_ns)
        assert dict(inner.stats)["slot"] == 1

    def test_obs_still_imports_without_jax(self):
        import subprocess

        code = ("import sys; "
                "from textsummarization_on_flink_tpu.obs import profile; "
                "from textsummarization_on_flink_tpu.obs.registry import "
                "Registry; "
                "p = profile.install_profiler(Registry()); "
                "c = p.phase('serve/pack'); c.__enter__(); "
                "c.__exit__(None, None, None); "
                "assert 'jax' not in sys.modules, 'obs pulled jax in'; "
                "assert c._ann is None")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]

    def test_null_brackets_are_shared_and_allocate_nothing(self):
        prof = profile_lib.NULL_PROFILER
        assert prof.phase("serve/pack", trace_id="t", slot=1) is \
            profile_lib.NULL_BRACKET
        assert prof.wall("serve/tick") is profile_lib.NULL_BRACKET

        def burst(n):
            for i in range(n):
                with prof.wall("serve/tick") as wall:
                    with prof.phase("serve/dispatch", fill=3) as ph:
                        pass
                    prof.observe_dispatch("serve/dispatch", "k", ph.dt)
                    wall.cancel()

        burst(64)
        gc.collect()
        before = sys.getallocatedblocks()
        burst(512)
        assert sys.getallocatedblocks() - before <= 16


# ---- the request's stage clock -----------------------------------------

class _StageEngine(_SimEngine):
    """_SimEngine with a prefill surface, so all five stages exist, and
    a little real time in each operation (the stage clock is the
    request's monotonic clock, not the profiler's)."""

    def prefill(self, example):
        time.sleep(0.001)
        return example

    def pack(self, idx, example):
        time.sleep(0.001)
        super().pack(idx, example)

    def unpack(self, idx, example):
        time.sleep(0.002)
        return super().unpack(idx, example)


STAGES = ("queue", "prefill", "slot_wait", "resident", "harvest")


class TestRequestStages:
    def _server(self, tmp_path, reg, **hps_kw):
        from textsummarization_on_flink_tpu.serve.server import (
            ServingServer,
        )
        vocab = Vocab(words=["w"])
        vclock = _VClock()
        hps = HParams(
            mode="decode", batch_size=2, vocab_size=vocab.size(),
            max_enc_steps=8, max_dec_steps=8, beam_size=2,
            min_dec_steps=1, max_oov_buckets=4, serve_max_queue=16,
            serve_mode="continuous", serve_slots=2,
            serve_refill_chunk=4, log_root=str(tmp_path),
            exp_name="stage_gate", **hps_kw)
        sim = _StageEngine(vclock, slots=2, chunk=4, steps_per_req=8,
                           step_cost_ms=5.0, pack_cost_ms=1.0)
        return ServingServer(hps, vocab, decoder=_NullDecoder(),
                             engine=sim, registry=reg, clock=vclock.now)

    def _stage(self, reg, stage):
        return reg.histogram("serve/request_stage_seconds").labels(
            stage=stage)

    def _events(self, tmp_path):
        by_uuid = {}
        for ln in open(tmp_path / "events.jsonl", encoding="utf-8"):
            r = json.loads(ln)
            if r.get("kind") == "request":
                by_uuid.setdefault(r["uuid"], []).append(r)
        return by_uuid

    def test_five_stages_sum_to_enqueue_to_resolve(self, tmp_path):
        reg = Registry()
        sink = obs.install_event_sink(str(tmp_path), flush_secs=0.05,
                                      reg=reg)
        server = self._server(tmp_path, reg)
        futures = [server.submit("w w w", uuid=f"s{i}") for i in range(5)]
        for _ in range(64):
            if all(f.done() for f in futures):
                break
            server.tick_once(poll=0.0)
        assert all(f.done() for f in futures)
        server.stop()
        sink.close()
        # in the registry: every stage observed once per request, and the
        # five sums add up to the latency histogram's (same marks)
        for s in STAGES:
            assert self._stage(reg, s).count == 5, s
        e2e = reg.histogram("serve/e2e_latency_seconds")
        assert e2e.count == 5
        assert sum(self._stage(reg, s).sum for s in STAGES) == \
            pytest.approx(e2e.sum, rel=1e-9)
        # the harvest is inside the latency now: the second request of a
        # harvest waited behind the first one's 2 ms unpack
        assert self._stage(reg, "harvest").snapshot()["max"] >= 0.004
        # in events.jsonl: each site carries its stage's milliseconds,
        # and per request they add up to enqueue -> resolve
        ms_of = {"admit": ["queue_ms"], "prefill": ["prefill_ms"],
                 "slot": ["slot_wait_ms"],
                 "finish": ["resident_ms", "harvest_ms"]}
        for uuid, events in self._events(tmp_path).items():
            total = 0.0
            for e in events:
                for key in ms_of.get(e["event"], []):
                    total += e["attrs"][key]
            fin = next(e for e in events if e["event"] == "finish")
            assert total == pytest.approx(fin["attrs"]["e2e_ms"],
                                          abs=0.004), uuid
            span_ms = (events[-1]["ts_us"] - events[0]["ts_us"]) / 1e3
            assert events[-1]["event"] == "resolve"
            assert total == pytest.approx(span_ms, abs=2.0), uuid

    def test_an_evicted_request_closes_the_stage_it_died_in(
            self, tmp_path):
        from textsummarization_on_flink_tpu.resilience.errors import (
            DeadlineExceededError,
        )
        reg = Registry()
        server = self._server(tmp_path, reg, decode_deadline_secs=0.03)
        # dies in the queue: only `queue` is ever observed for it
        dead = server.submit("w w w", uuid="dead")
        time.sleep(0.04)
        server.tick_once(poll=0.0)
        with pytest.raises(DeadlineExceededError):
            dead.result(timeout=0)
        assert {s: self._stage(reg, s).count for s in STAGES} == {
            "queue": 1, "prefill": 0, "slot_wait": 0, "resident": 0,
            "harvest": 0}
        # dies resident: closes `resident` at the eviction, never
        # `harvest`
        slow = server.submit("w w w", uuid="slow")
        server.tick_once(poll=0.0)  # admitted, one chunk of two
        assert not slow.done()
        time.sleep(0.04)
        server.tick_once(poll=0.0)
        with pytest.raises(DeadlineExceededError):
            slow.result(timeout=0)
        server.stop()
        assert {s: self._stage(reg, s).count for s in STAGES} == {
            "queue": 2, "prefill": 1, "slot_wait": 1, "resident": 1,
            "harvest": 0}
