"""Performance attribution plane (ISSUE 16; OBSERVABILITY.md
"Performance attribution").

Three ledgers behind one per-registry ``Profiler`` (attached at
``registry.profile``, first-install-wins like the SLO engine):

  * **phase ledger** — dispatch-boundary timers around the serve and
    train hot paths (prefill / pack / decode chunk / harvest / evict in
    the continuous path, per-tier micro-batch dispatch, and the train
    loop's host-wait / step-dispatch / metrics-flush / checkpoint
    sub-phases), aggregated into the labeled ``profile/phase_seconds``
    histogram plus a phases-sum-to-wall accounting check
    (``profile/phase_coverage_ratio``).  The clock is injectable so the
    tier-1 gate drives it in virtual time.  ``Profiler.phase(name)`` is
    the ONE bracket the hot paths use (``compiled_call(phase=...)``
    opens one around its call): it books this ledger, records
    the ``obs.spans`` span and opens a ``jax.profiler.TraceAnnotation``
    for the same interval, so a profiler capture holds the host phases
    on the device trace's own clock.  A phase opened inside another on
    the same thread is a CHILD: it gets its own label, span and
    annotation and is left out of the coverage sum and the recent ring.
  * **compile ledger** — the ONE shared jit-cache-diff helper
    (``compiled_call``) the decode paths route through, recording every
    compile event (site, shape/bucket key, wall duration, warm-set
    size) and firing a ``compile_storm`` flight dump + /alerts entry
    when a site's compile count exceeds its committed budget (warm set
    = 4 decode kernels + one prefill per bucket + one spec kernel per
    k).  The compile-once invariant becomes runtime-monitored, not just
    test-pinned.
  * **divergence sentinel** — per dispatch shape, the executed
    program's analytic cost (``__graft_entry__.decode_step_cost`` /
    ``prefill_cost`` / ``train_step_cost``) is priced ONCE off the hot
    path (the helpers AOT-compile, so pricing runs on a daemon thread;
    ``hps.profile_analytic`` gates it); each dispatch then publishes
    achieved bytes/s and FLOPs/s gauges and fires a ``perf_divergence``
    flight dump when throughput drops below the warm per-shape baseline
    by more than ``hps.profile_divergence_factor``.

Exposition: ``profile_payload(registry)`` backs the read-only
``/profile`` endpoint (phase table, compile ledger, top-k slowest
dispatches with trace exemplar ids for scripts/trace_summary.py);
``profile_alerts(registry)`` rides the /alerts scrape.  Both serve
state cached on the record side — a scrape never mutates or pays dump
I/O (the /alerts discipline from obs/slo.py).

Null path: a dark registry (``hps.obs=False``) gets the shared
``NULL_PROFILER`` whose methods return constants — no per-dispatch
allocation (pinned in tests/test_profile.py).
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from textsummarization_on_flink_tpu.obs import flightrec
from textsummarization_on_flink_tpu.obs import spans as spans_lib
from textsummarization_on_flink_tpu.obs.registry import (
    Registry,
    exponential_buckets,
)

#: bounded ring of recent phase records — feeds the /profile top-k
#: slowest-dispatch table and the windowed coverage check in tests
RECENT_PHASES_CAP = 512
#: bounded compile-event history for /profile
COMPILE_EVENTS_CAP = 256
#: ledger notes (profiler captures, budget registrations) kept
NOTES_CAP = 64
#: dispatches that establish a shape's warm throughput baseline before
#: the divergence sentinel starts judging (the first dispatch carries
#: the compile, so the baseline is the BEST of the first N, not the
#: first)
BASELINE_SAMPLES = 3
#: default measured-vs-baseline wall inflation that fires the
#: ``perf_divergence`` dump (overridden by hps.profile_divergence_factor)
DEFAULT_DIVERGENCE_FACTOR = 5.0
#: profile/phase_seconds and profile/wall_seconds bounds: a quarter-octave
#: wide from 10 us to ~141 s (the stage clock's rule, serve/batcher.py),
#: so a percentile read from the buckets is within 19% of the value
PHASE_BUCKETS = exponential_buckets(1e-5, 2.0 ** 0.25, 96)


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` where jax is ALREADY loaded in
    this process, else None: obs/ imports no jax, and a jax-free process
    (the virtual-time gates, the fleet router) has no profiler whose
    host plane an annotation could land in."""
    jax = sys.modules.get("jax")
    return getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)


class _NullBracket:
    """The dark registry's phase()/wall(): one shared do-nothing
    context."""

    __slots__ = ()

    dt = 0.0

    def __enter__(self) -> "_NullBracket":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass

    def cancel(self) -> None:
        pass


NULL_BRACKET = _NullBracket()


class _Phase:
    """One open ``Profiler.phase``: ledger + span + annotation over the
    same interval.  ``dt`` holds the booked duration after exit."""

    __slots__ = ("_prof", "name", "_trace_id", "_span", "_ann", "_t0", "dt")

    def __init__(self, prof: "Profiler", name: str,
                 trace_id: Optional[str], parent, attrs: Dict[str, Any]):
        self._prof = prof
        self.name = name
        self._trace_id = trace_id
        self._span = spans_lib.span(prof._reg, name, parent=parent, **attrs)
        ann = _trace_annotation()
        self._ann = ann(name, **attrs) if ann is not None else None
        self._t0 = 0.0
        self.dt = 0.0

    def __enter__(self) -> "_Phase":
        self._prof._local.depth = self._prof._depth() + 1
        self._span.__enter__()
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = self._prof._clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        prof = self._prof
        prof._local.depth = prof._depth() - 1
        # booked on a raising exit too: the failed work took the time
        self.dt = prof.end(self.name, self._t0, trace_id=self._trace_id)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        self._span.__exit__(exc_type, exc, tb)


class _Wall:
    """One open ``Profiler.wall``: the wall ledger plus an annotation
    (no span: a wall is the denominator, not an attributable phase)."""

    __slots__ = ("_prof", "name", "_ann", "_t0", "_keep")

    def __init__(self, prof: "Profiler", name: str):
        self._prof = prof
        self.name = name
        ann = _trace_annotation()
        self._ann = ann(name) if ann is not None else None
        self._t0 = 0.0
        self._keep = True

    def cancel(self) -> None:
        """Leave this unit out of the wall ledger (an idle serve tick:
        its queue poll is idleness, not a phase to fix)."""
        self._keep = False

    def __enter__(self) -> "_Wall":
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = self._prof._clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._keep:
            self._prof.end_wall(self.name, self._t0)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)


class _NullProfiler:
    """Shared do-nothing profiler for dark registries: every method
    returns a preexisting constant, so the ``obs=False`` path adds no
    per-dispatch allocation (the null-object contract of
    NULL_COUNTER/NULL_GAUGE — pinned by test_profile)."""

    __slots__ = ()

    def phase(self, name, trace_id=None, parent=None,
              **attrs) -> _NullBracket:
        return NULL_BRACKET

    def wall(self, name) -> _NullBracket:
        return NULL_BRACKET

    def start(self) -> float:
        return 0.0

    def end(self, phase, t0, trace_id=None) -> float:
        return 0.0

    def end_wall(self, name, t0) -> float:
        return 0.0

    def set_compile_budget(self, site, budget) -> None:
        pass

    def record_compile(self, site, key, dur_s) -> None:
        pass

    def record_hit(self, site) -> None:
        pass

    def register_cost(self, site, key, provider) -> None:
        pass

    def prime_cost(self, site, key, flops, bytes_) -> None:
        pass

    def observe_dispatch(self, site, key, wall_s, trace_id=None) -> None:
        pass

    def note(self, kind, **fields) -> None:
        pass

    def phase_stats(self) -> Dict[str, Tuple[int, float, float]]:
        return {}

    def compile_stats(self) -> Dict[str, Dict[str, Any]]:
        return {}

    def coverage(self) -> float:
        return 0.0


NULL_PROFILER = _NullProfiler()


class Profiler:
    """Per-registry performance attribution state (phase ledger +
    compile ledger + divergence sentinel).  All record paths run on
    dispatch threads, so they take one short lock, touch no device
    values, and never raise past telemetry."""

    def __init__(self, registry: Registry,
                 clock: Callable[[], float] = time.perf_counter,
                 divergence_factor: float = DEFAULT_DIVERGENCE_FACTOR):
        self._reg = registry
        self._clock = clock
        self._div_factor = max(float(divergence_factor), 1.0)
        self._lock = threading.Lock()
        # phase ledger: name -> [count, total_s, max_s]; walls likewise
        self._phases: Dict[str, List[float]] = {}
        self._walls: Dict[str, List[float]] = {}
        # seconds booked by CHILD phases (a phase() open on the thread
        # when they closed): in the table above, out of the coverage sum
        self._nested_s = 0.0
        self._local = threading.local()  # .depth: phase()s open here
        self._recent: List[Tuple[int, str, float, Optional[str]]] = []
        # compile ledger: site -> {compiles, hits, keys, last_dur_s}
        self._sites: Dict[str, Dict[str, Any]] = {}
        self._budgets: Dict[str, int] = {}
        self._compile_events: List[Dict[str, Any]] = []
        self._storm: Optional[Dict[str, Any]] = None
        # divergence sentinel: (site, key) -> cost/baseline state
        self._costs: Dict[Tuple[str, Any], Dict[str, float]] = {}
        self._pricing: set = set()
        self._div: Dict[Tuple[str, Any], Dict[str, float]] = {}
        self._notes: List[Dict[str, Any]] = []
        # metric families (literal names — the doc-drift gate reads the
        # source): a child per label value, created on its first record
        # and kept here, so a bracket's exit does no labels() lookup
        self._h_phase = registry.histogram("profile/phase_seconds",
                                           buckets=PHASE_BUCKETS)
        self._h_wall = registry.histogram("profile/wall_seconds",
                                          buckets=PHASE_BUCKETS)
        self._phase_series: Dict[str, Any] = {}
        self._wall_series: Dict[str, Any] = {}
        self._g_coverage = registry.gauge("profile/phase_coverage_ratio")
        self._c_compiles = registry.counter("profile/compile_events_total")
        self._h_compile = registry.histogram("profile/compile_seconds")
        self._c_storms = registry.counter("profile/compile_storms_total")
        self._g_bps = registry.gauge("profile/achieved_bytes_per_second")
        self._g_fps = registry.gauge("profile/achieved_flops_per_second")
        self._c_div = registry.counter("profile/divergence_dumps_total")

    # -- phase ledger ---------------------------------------------------
    def phase(self, name: str, trace_id: Optional[str] = None,
              parent: Optional[spans_lib.TraceContext] = None,
              **attrs: Any) -> _Phase:
        """The one bracket around a hot-path interval::

            with prof.phase("serve/dispatch", fill=n) as ph:
                ...
            prof.observe_dispatch(site, key, ph.dt)

        books the phase ledger (`trace_id` is the histogram exemplar),
        records the ``obs.spans`` span (`parent` links it into a
        request's trace; `attrs` ride on it) and, where jax is loaded,
        opens a ``jax.profiler.TraceAnnotation(name, **attrs)`` — so a
        capture shows the phase on the thread that ran it, on the device
        trace's clock."""
        return _Phase(self, name, trace_id, parent, attrs)

    def wall(self, name: str) -> _Wall:
        """The bracket around one WALL unit (a serve tick, a train
        round) — the denominator of the phases-sum-to-wall check."""
        return _Wall(self, name)

    def _depth(self) -> int:
        return getattr(self._local, "depth", 0)

    def start(self) -> float:
        """A phase/wall start token (the injected clock's now)."""
        return self._clock()

    def end(self, phase: str, t0: float,
            trace_id: Optional[str] = None) -> float:
        """Close one phase opened by start(); returns its duration.  A
        phase closed while a phase() is open on this thread is a child:
        same table and histogram, no share of coverage or of the ring
        (its parent already accounts for the interval)."""
        dt = self._clock() - t0
        child = self._depth() > 0
        with self._lock:
            agg = self._phases.get(phase)
            if agg is None:
                agg = self._phases[phase] = [0, 0.0, 0.0]
            agg[0] += 1
            agg[1] += dt
            if dt > agg[2]:
                agg[2] = dt
            if child:
                self._nested_s += dt
            else:
                # serialized epoch stamp only
                self._recent.append((int(time.time() * 1e6), phase, dt,
                                     trace_id))
                if len(self._recent) > RECENT_PHASES_CAP:
                    del self._recent[:len(self._recent) - RECENT_PHASES_CAP]
        h = self._phase_series.get(phase)
        if h is None:
            h = self._phase_series[phase] = self._h_phase.labels(phase=phase)
        h.observe(dt, trace_id=trace_id)
        return dt

    def end_wall(self, name: str, t0: float) -> float:
        """Close one WALL unit (a serve tick, a train round) — the
        denominator of the phases-sum-to-wall accounting check."""
        dt = self._clock() - t0
        with self._lock:
            agg = self._walls.get(name)
            if agg is None:
                agg = self._walls[name] = [0, 0.0, 0.0]
            agg[0] += 1
            agg[1] += dt
            if dt > agg[2]:
                agg[2] = dt
            cov = self._coverage_locked()
        h = self._wall_series.get(name)
        if h is None:
            h = self._wall_series[name] = self._h_wall.labels(wall=name)
        h.observe(dt)
        self._g_coverage.set(cov)
        return dt

    def _coverage_locked(self) -> float:
        wall = sum(w[1] for w in self._walls.values())
        if wall <= 0.0:
            return 0.0
        return (sum(p[1] for p in self._phases.values())
                - self._nested_s) / wall

    def coverage(self) -> float:
        """sum(phase time) / sum(wall time) — the accounting check."""
        with self._lock:
            return self._coverage_locked()

    def phase_stats(self) -> Dict[str, Tuple[int, float, float]]:
        """{phase: (count, total_s, max_s)} snapshot (bench evidence
        fields diff this across the timed window)."""
        with self._lock:
            return {k: (int(v[0]), v[1], v[2])
                    for k, v in self._phases.items()}

    def recent_phases(self) -> List[Tuple[int, str, float, Optional[str]]]:
        """Copy of the bounded (ts_us, phase, dur_s, trace_id) ring."""
        with self._lock:
            return list(self._recent)

    # -- compile ledger -------------------------------------------------
    def set_compile_budget(self, site: str, budget: int) -> None:
        """Commit a site's warm-set budget: compiles beyond it are a
        compile storm (dump + /alerts).  Re-registration keeps the MAX
        so a widened engine never shrinks an already-committed budget."""
        with self._lock:
            prev = self._budgets.get(site)
            if prev is None or budget > prev:
                self._budgets[site] = int(budget)

    def record_hit(self, site: str) -> None:
        with self._lock:
            st = self._site_locked(site)
            st["hits"] += 1

    def _site_locked(self, site: str) -> Dict[str, Any]:
        st = self._sites.get(site)
        if st is None:
            st = self._sites[site] = {"compiles": 0, "hits": 0,
                                      "keys": set(), "last_dur_s": 0.0}
        return st

    def record_compile(self, site: str, key: Any, dur_s: float) -> None:
        """One compile event (a jit-cache MISS observed by
        compiled_call, or reported directly by an engine)."""
        ts_us = int(time.time() * 1e6)
        storm: Optional[Dict[str, Any]] = None
        with self._lock:
            st = self._site_locked(site)
            st["compiles"] += 1
            st["keys"].add(key)
            st["last_dur_s"] = dur_s
            warm = sum(s["compiles"] for s in self._sites.values())
            self._compile_events.append({
                "site": site, "key": str(key), "dur_s": round(dur_s, 6),
                "warm_set": warm, "ts_us": ts_us})
            if len(self._compile_events) > COMPILE_EVENTS_CAP:
                del self._compile_events[
                    :len(self._compile_events) - COMPILE_EVENTS_CAP]
            budget = self._budgets.get(site)
            if budget is not None and st["compiles"] > budget:
                storm = {"site": site, "key": str(key),
                         "compiles": st["compiles"], "budget": budget,
                         "warm_set": warm, "ts_us": ts_us}
                self._storm = storm
        self._c_compiles.labels(site=site).inc()
        self._h_compile.observe(dur_s)
        if storm is not None:
            # trigger OUTSIDE the lock: the dump walks the flight ring
            self._c_storms.inc()
            flightrec.trigger(self._reg, "compile_storm", **storm)

    def compile_stats(self) -> Dict[str, Dict[str, Any]]:
        """{site: {compiles, hits, keys, budget, last_dur_s}} snapshot
        — the one source of truth the warm-set test pins assert
        through."""
        with self._lock:
            return {site: {"compiles": st["compiles"], "hits": st["hits"],
                           "keys": sorted(str(k) for k in st["keys"]),
                           "budget": self._budgets.get(site),
                           "last_dur_s": st["last_dur_s"]}
                    for site, st in self._sites.items()}

    def warm_set_size(self) -> int:
        with self._lock:
            return sum(st["compiles"] for st in self._sites.values())

    # -- divergence sentinel --------------------------------------------
    def prime_cost(self, site: str, key: Any, flops: float,
                   bytes_: float) -> None:
        """Install one shape's analytic cost synchronously (tests and
        callers that already hold the numbers)."""
        with self._lock:
            self._costs[(site, key)] = {"flops": float(flops),
                                        "bytes": float(bytes_)}

    def register_cost(self, site: str, key: Any,
                      provider: Callable[[], Dict[str, float]]) -> None:
        """Price one dispatch shape ONCE, off the hot path: `provider`
        (typically a __graft_entry__ cost helper closure, which
        AOT-compiles) runs on a daemon thread; until it lands the
        sentinel simply stays quiet for that shape.  A failing provider
        leaves the shape unpriced — pricing must never break serving."""
        with self._lock:
            ck = (site, key)
            if ck in self._costs or ck in self._pricing:
                return
            self._pricing.add(ck)

        def _price() -> None:
            try:
                cost = provider()
                flops = float(cost.get("flops", 0.0))
                bytes_ = float(cost.get("bytes", 0.0))
            except Exception:  # tslint: disable=TS005 — analytic pricing is best-effort telemetry; a failed import/compile must not surface
                flops = bytes_ = 0.0
            with self._lock:
                self._pricing.discard(ck)
                if flops > 0.0 or bytes_ > 0.0:
                    self._costs[ck] = {"flops": flops, "bytes": bytes_}

        threading.Thread(target=_price, daemon=True,
                         name=f"profile-pricer-{site}").start()

    def observe_dispatch(self, site: str, key: Any, wall_s: float,
                        trace_id: Optional[str] = None) -> None:
        """One measured dispatch of a priced shape: publish achieved
        throughput (analytic cost / measured wall) and fire the
        ``perf_divergence`` dump when it falls below the warm baseline
        by more than the committed factor."""
        if wall_s <= 0.0:
            return
        fire: Optional[Dict[str, Any]] = None
        with self._lock:
            cost = self._costs.get((site, key))
            if cost is None:
                return
            bps = cost["bytes"] / wall_s
            fps = cost["flops"] / wall_s
            st = self._div.get((site, key))
            if st is None:
                st = self._div[(site, key)] = {"samples": 0,
                                               "baseline_bps": 0.0,
                                               "drift": 1.0}
            st["samples"] += 1
            st["bps"] = bps
            st["fps"] = fps
            st["wall_s"] = wall_s
            if st["samples"] <= BASELINE_SAMPLES:
                # warmup window: the first dispatch carries the compile,
                # so the baseline is the BEST achieved throughput seen
                if bps > st["baseline_bps"]:
                    st["baseline_bps"] = bps
            elif bps * self._div_factor < st["baseline_bps"]:
                st["drift"] = st["baseline_bps"] / max(bps, 1e-12)
                fire = {"site": site, "key": str(key),
                        "wall_s": round(wall_s, 6),
                        "achieved_bytes_per_s": round(bps, 3),
                        "baseline_bytes_per_s": round(st["baseline_bps"], 3),
                        "drift": round(st["drift"], 3),
                        "trace_id": trace_id}
            else:
                st["drift"] = st["baseline_bps"] / max(bps, 1e-12)
        self._g_bps.labels(site=site).set(bps)
        self._g_fps.labels(site=site).set(fps)
        if fire is not None:
            self._c_div.inc()
            flightrec.trigger(self._reg, "perf_divergence", **fire)

    # -- ledger notes ---------------------------------------------------
    def note(self, kind: str, **fields: Any) -> None:
        """A non-metric ledger event (e.g. a jax.profiler capture
        window), kept in a bounded ring for /profile."""
        rec = {"note": kind, "ts_us": int(time.time() * 1e6), **fields}
        with self._lock:
            self._notes.append(rec)
            if len(self._notes) > NOTES_CAP:
                del self._notes[:len(self._notes) - NOTES_CAP]
        # the frame kind is the ring's discriminator; the note's own
        # kind rides as the `note` field
        flightrec.record(self._reg, "profile_note", **rec)

    # -- exposition (read-only snapshots) -------------------------------
    def payload(self, top_k: int = 8) -> Dict[str, Any]:
        """The /profile body: phase table, wall/coverage accounting,
        compile ledger, divergence table, top-k slowest dispatches (with
        trace exemplar ids that paste into scripts/trace_summary.py
        --request), and ledger notes.  Pure read under one lock."""
        with self._lock:
            phases = [{"phase": k, "count": int(v[0]),
                       "total_s": round(v[1], 6), "max_s": round(v[2], 6),
                       "mean_ms": round(1e3 * v[1] / v[0], 3) if v[0]
                       else 0.0}
                      for k, v in sorted(self._phases.items())]
            walls = [{"wall": k, "count": int(v[0]),
                      "total_s": round(v[1], 6), "max_s": round(v[2], 6)}
                     for k, v in sorted(self._walls.items())]
            coverage = self._coverage_locked()
            sites = {site: {"compiles": st["compiles"], "hits": st["hits"],
                            "keys": sorted(str(k) for k in st["keys"]),
                            "budget": self._budgets.get(site),
                            "last_dur_s": round(st["last_dur_s"], 6)}
                     for site, st in sorted(self._sites.items())}
            warm = sum(st["compiles"] for st in self._sites.values())
            events = list(self._compile_events[-32:])
            storm = dict(self._storm) if self._storm else None
            divergence = [{"site": site, "key": str(key),
                           "flops": self._costs[(site, key)]["flops"],
                           "bytes": self._costs[(site, key)]["bytes"],
                           "samples": int(st.get("samples", 0)),
                           "achieved_bytes_per_s": round(
                               st.get("bps", 0.0), 3),
                           "achieved_flops_per_s": round(
                               st.get("fps", 0.0), 3),
                           "baseline_bytes_per_s": round(
                               st.get("baseline_bps", 0.0), 3),
                           "drift": round(st.get("drift", 1.0), 3)}
                          for (site, key), st in sorted(
                              self._div.items(), key=lambda kv: str(kv[0]))]
            slowest = sorted(self._recent, key=lambda r: -r[2])[:top_k]
            notes = list(self._notes)
        return {
            "phases": phases,
            "walls": walls,
            "coverage": round(coverage, 4),
            "compile_ledger": {"warm_set": warm, "sites": sites,
                               "events": events, "storm": storm},
            "divergence": divergence,
            "slowest": [{"phase": p, "dur_s": round(d, 6),
                         "trace_id": t, "ts_us": ts}
                        for ts, p, d, t in slowest],
            "notes": notes,
        }

    def alerts(self) -> Dict[str, Any]:
        """The /alerts contribution: cached storm + divergence state,
        served without touching the record path (read-only scrape)."""
        with self._lock:
            storm = dict(self._storm) if self._storm else None
            diverged = [{"site": site, "key": str(key),
                         "drift": round(st.get("drift", 1.0), 3)}
                        for (site, key), st in self._div.items()
                        if st.get("drift", 1.0) > self._div_factor]
        return {"installed": True, "compile_storm": storm,
                "divergence": diverged}


_INSTALL_LOCK = threading.Lock()


def install_profiler(registry: Registry,
                     clock: Callable[[], float] = time.perf_counter,
                     divergence_factor: float = DEFAULT_DIVERGENCE_FACTOR,
                     ):
    """Attach a Profiler to `registry` (first install wins, like
    install_slo_engine); returns the installed profiler.  A disabled
    registry gets the shared NULL_PROFILER."""
    if registry is None or not registry.enabled:
        return NULL_PROFILER
    prof = getattr(registry, "profile", None)
    if prof is None:
        with _INSTALL_LOCK:
            prof = getattr(registry, "profile", None)
            if prof is None:
                prof = Profiler(registry, clock=clock,
                                divergence_factor=divergence_factor)
                registry.profile = prof
    return prof


def profiler_for(registry: Optional[Registry]):
    """The registry's profiler (installing one with the default clock
    on first use), or NULL_PROFILER for a dark/absent registry."""
    if registry is None or not registry.enabled:
        return NULL_PROFILER
    prof = getattr(registry, "profile", None)
    if prof is not None:
        return prof
    return install_profiler(registry)


def compiled_call(registry: Optional[Registry], site: str, fn: Callable,
                  *args: Any, key: Any = "", phase: Optional[str] = None,
                  **kw: Any) -> Any:
    """Run a jitted callable with compile-ledger accounting: the ONE
    replacement for the hand-rolled ``fn._cache_size()`` diff blocks
    the decode paths used to carry (decode/beam_search.py,
    decode/speculative.py, decode/decoder.py).  Cache growth across the
    call = a fresh trace/compile; hit/miss lands in the established
    ``decode/compile_cache_*_total`` counters AND the compile ledger,
    and `phase` (when given) opens a ``Profiler.phase`` around the call
    — ledger, span and annotation — whose booked wall the compile
    ledger reuses: one timing, both ledgers."""
    try:  # private jax API; telemetry must never break the dispatch
        before = fn._cache_size()
    except Exception:  # tslint: disable=TS005 — _cache_size is a private jax API; absent on some builds
        before = None
    prof = profiler_for(registry)
    if phase is None:
        t0 = prof.start()
        out = fn(*args, **kw)
        dt = prof.start() - t0
    else:
        with prof.phase(phase) as ph:
            out = fn(*args, **kw)
        dt = ph.dt
    if before is not None:
        try:
            missed = fn._cache_size() > before
            if registry is not None:
                registry.counter(
                    "decode/compile_cache_misses_total" if missed
                    else "decode/compile_cache_hits_total").inc()
            if missed:
                prof.record_compile(site, key, dt)
            else:
                prof.record_hit(site)
        except Exception:  # tslint: disable=TS005 — best-effort cache telemetry; the result is already in hand
            pass
    return out


def profile_payload(registry: Optional[Registry]) -> Dict[str, Any]:
    """The /profile endpoint body.  Quiet {installed: False} when no
    profiler has recorded on this registry."""
    prof = getattr(registry, "profile", None) if registry is not None \
        else None
    if prof is None or prof is NULL_PROFILER:
        return {"installed": False, "phases": [], "walls": [],
                "coverage": 0.0,
                "compile_ledger": {"warm_set": 0, "sites": {},
                                   "events": [], "storm": None},
                "divergence": [], "slowest": [], "notes": []}
    return {"installed": True, **prof.payload()}


def profile_alerts(registry: Optional[Registry]) -> Dict[str, Any]:
    """The profiler's /alerts contribution (merged by obs/http.py under
    the "profile" key).  Read-only; quiet when not installed."""
    prof = getattr(registry, "profile", None) if registry is not None \
        else None
    if prof is None or prof is NULL_PROFILER:
        return {"installed": False, "compile_storm": None,
                "divergence": []}
    return prof.alerts()
