"""Fleet smoke (ISSUE 13 + 17): N ServingServer replicas behind the
REAL FleetRouter on a real tiny model — kill one replica mid-decode
under load and prove the fleet contract end to end:

  * every admitted request resolves EXACTLY ONCE (no lost futures, no
    duplicates) even though a replica died holding residents and queued
    requests — the orphans requeue on survivors through the typed
    ``ReplicaKilledError`` path (``serve/requeued_total``);
  * the answers are ROW-IDENTICAL to a single-server run of the same
    requests (same params -> same summaries, whichever replica decoded
    them — failover must not change output).

Two transports, same contract:

  * ``--transport=inproc`` (default): three in-process replicas, the
    kill is ``router.kill_replica`` (ISSUE 13).
  * ``--transport=proc`` (ISSUE 17): three SUPERVISED OS CHILD
    PROCESSES (``cli.py serve-replica``) behind the same router over
    the socket transport — the kill is a REAL SIGKILL on a live pid
    mid-decode (direct, or via the armed ``serve.proc_kill`` chaos
    point when TS_FAULTS carries it — scripts/chaos.sh's sweep).  On
    top of the inproc assertions this proves: the victim RESTARTS
    under supervision and is READMITTED through the rotation breaker's
    half-open probe, and the requeued work is witnessed in the
    SURVIVING children's events.jsonl — the SIGKILLed child wrote
    nothing, so the ledger reconstructs from the supervisor's view
    alone.

The deterministic virtual-time scenarios (rolling-swap p99 ratio,
hedge win/rate gate, socket/scrape overhead ceilings) are committed in
SERVE_SLO.json and enforced by tests/test_serve_slo.py; this smoke
proves the THREADED production paths run on a real model.  Wired into
scripts/repro.sh (both transports).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import json  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402

from textsummarization_on_flink_tpu import obs  # noqa: E402
from textsummarization_on_flink_tpu.config import HParams  # noqa: E402
from textsummarization_on_flink_tpu.data.vocab import Vocab  # noqa: E402
from textsummarization_on_flink_tpu.obs import Registry  # noqa: E402
from textsummarization_on_flink_tpu.resilience import (  # noqa: E402
    faultinject,
)
from textsummarization_on_flink_tpu.serve.fleet import (  # noqa: E402
    FleetRouter,
)
from textsummarization_on_flink_tpu.serve.server import (  # noqa: E402
    ServingServer,
)
from textsummarization_on_flink_tpu.train import trainer  # noqa: E402

N_ROWS, N_REPLICAS = 12, 3
WORDS = ["article", "reference", "."] + [str(i) for i in range(N_ROWS)]


def _rows():
    return [(f"uuid-{i}",
             f"article {i} ." if i % 2 == 0
             else f"article {i} " + ". article " * 5 + ".",
             "", f"reference {i} .")
            for i in range(N_ROWS)]


def _hps(vocab, **overrides):
    base = dict(mode="decode", batch_size=2, hidden_dim=16, emb_dim=8,
                vocab_size=vocab.size(), max_enc_steps=16, max_dec_steps=6,
                beam_size=2, min_dec_steps=1, max_oov_buckets=4,
                serve_max_queue=64, serve_buckets="8,16",
                serve_mode="continuous", serve_slots=2,
                serve_refill_chunk=2, serve_replicas=N_REPLICAS, seed=0)
    base.update(overrides)
    return HParams(**base)


def _solo_baseline(hps, vocab, params, rows):
    """Single-server run: the answers failover must reproduce."""
    baseline = {}
    solo = ServingServer(
        hps, vocab, params=params,
        decode_root=tempfile.mkdtemp(prefix="fleet_smoke_solo_"))
    with solo:
        futs = [solo.submit(a, uuid=u, reference=r)
                for u, a, _, r in rows]
        for f in futs:
            res = f.result(timeout=600)
            baseline[res.uuid] = res.as_row()
    assert len(baseline) == len(rows)
    return baseline


def run_inproc() -> None:
    rows = _rows()
    vocab = Vocab(words=WORDS)
    hps = _hps(vocab)
    params = trainer.init_train_state(hps, vocab.size(), seed=0).params
    baseline = _solo_baseline(hps, vocab, params, rows)

    # the fleet: per-replica registries (gauge isolation), the router on
    # the process default so its counters land where we can read them
    servers = [ServingServer(
        hps, vocab, params=params, registry=Registry(),
        decode_root=tempfile.mkdtemp(prefix=f"fleet_smoke_r{i}_"))
        for i in range(N_REPLICAS)]
    router = FleetRouter(servers, hps, registry=obs.registry())
    got = {}
    with router:
        futs = [router.submit(a, uuid=u, reference=r)
                for u, a, _, r in rows]
        # kill the most-loaded replica while its work is in flight
        victim = max((h for h in router.replicas() if not h.killed),
                     key=lambda h: h.load())
        assert victim.load() > 0, "fleet drained before the kill (smoke " \
            "needs the victim mid-decode; raise N_ROWS)"
        router.kill_replica(victim.rid)
        for f in futs:
            got[f.uuid] = f.result(timeout=600).as_row()

    reg = obs.registry()
    kills = int(reg.counter("serve/replica_kills_total").value)
    requeued = int(reg.counter("serve/requeued_total").value)
    assert kills == 1, kills
    assert requeued >= 1, (
        "the killed replica held no admitted work — not a failover test")
    # exactly once: one resolution per admitted uuid, none lost
    assert sorted(got) == sorted(baseline), (
        sorted(set(baseline) - set(got)), sorted(set(got) - set(baseline)))
    # row parity: failover (and routing) must not change the answers
    drift = [u for u in baseline if got[u] != baseline[u]]
    assert not drift, f"fleet/single-server row drift on {drift}"
    print(f"fleet smoke OK: {N_ROWS} rows over {N_REPLICAS} replicas, "
          f"replica {victim.rid} killed under load, {requeued} request(s) "
          f"requeued on survivors, every future resolved exactly once, "
          f"rows identical to the single-server run")


def _finished_uuids(events_path):
    """The uuids with a ``finish`` lifecycle record in one replica's
    events.jsonl (missing/partial files yield what they hold)."""
    done = set()
    try:
        with open(events_path, "r", encoding="utf-8") as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if (rec.get("kind") == "request"
                        and rec.get("event") == "finish"):
                    done.add(rec.get("uuid"))
    except OSError:
        pass
    return done


def run_proc() -> None:
    from textsummarization_on_flink_tpu.serve.procfleet import ProcFleet

    rows = _rows()
    vocab = Vocab(words=WORDS)
    workdir = tempfile.mkdtemp(prefix="fleet_smoke_proc_")
    # the children rebuild the IDENTICAL vocab from this file (same
    # word order -> same ids) and the IDENTICAL params from seed 0
    vocab_path = os.path.join(workdir, "vocab")
    with open(vocab_path, "w", encoding="utf-8") as f:
        for w in WORDS:
            f.write(f"{w} 1\n")
    hps = _hps(vocab, vocab_path=vocab_path, log_root=workdir,
               exp_name="smoke")
    params = trainer.init_train_state(hps, vocab.size(), seed=0).params
    baseline = _solo_baseline(hps, vocab, params, rows)

    reg = obs.registry()
    chaos = faultinject.plan().armed("serve.proc_kill")
    fleet = ProcFleet(hps, registry=reg, state_dir=workdir,
                      ready_timeout=300.0, replica_reset_secs=0.5,
                      restart_max_delay=0.5)
    got = {}
    fleet.start()
    assert fleet.wait_ready(timeout=300.0), (
        "process fleet failed to become ready: "
        f"{[(p.rid, p.state) for p in fleet.procs]}")
    incarnations = {p.rid: p.incarnation for p in fleet.procs}
    try:
        futs = [fleet.router.submit(a, uuid=u, reference=r)
                for u, a, _, r in rows]
        if chaos:
            # armed serve.proc_kill: the supervision thread SIGKILLs
            # the most-loaded live child once load exists; wait for it
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                if any(p.deaths for p in fleet.procs):
                    break
                time.sleep(0.02)
            dead = [p for p in fleet.procs if p.deaths]
            assert dead, "serve.proc_kill armed but no child died"
            victim = dead[0]
        else:
            victim = max(fleet.procs,
                         key=lambda p: fleet.remotes[
                             fleet.procs.index(p)].load())
            vload = fleet.remotes[fleet.procs.index(victim)].load()
            assert vload > 0, "fleet drained before the kill (smoke " \
                "needs the victim mid-decode; raise N_ROWS)"
            assert victim.kill_now(), "victim child was not alive"
        for f in futs:
            got[f.uuid] = f.result(timeout=600).as_row()
        # the victim must restart under supervision and rejoin the
        # rotation through the breaker's half-open probe
        vh = next(h for h in fleet.handles if h.rid == victim.rid)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if victim.ready() and vh.in_rotation():
                break
            time.sleep(0.05)
        assert victim.incarnation > incarnations[victim.rid], (
            f"victim {victim.rid} was never restarted")
        assert victim.ready() and vh.in_rotation(), (
            f"victim {victim.rid} not readmitted: state={victim.state} "
            f"breaker={vh.breaker.state}")
    finally:
        fleet.stop(timeout=60.0)

    requeued = int(reg.counter("serve/requeued_total").value)
    deaths = sum(p.deaths for p in fleet.procs)
    assert deaths >= 1, "no child death recorded"
    assert requeued >= 1, (
        "the SIGKILLed child held no admitted work — not a failover test")
    assert sorted(got) == sorted(baseline), (
        sorted(set(baseline) - set(got)), sorted(set(got) - set(baseline)))
    drift = [u for u in baseline if got[u] != baseline[u]]
    assert not drift, f"proc-fleet/single-server row drift on {drift}"
    # the survivors' ledgers are the proof: every uuid finished in SOME
    # child's events.jsonl, and the victim's own ledger (it was
    # SIGKILLed — anything unflushed is gone) cannot account for all of
    # them, so the difference decoded on surviving replicas
    finished = {}
    for p in fleet.procs:
        finished[p.rid] = _finished_uuids(os.path.join(
            workdir, "smoke", f"replica-{p.rid}", "events.jsonl"))
    survivors_finished = set()
    for rid, done in finished.items():
        if rid != victim.rid:
            survivors_finished |= done
    assert survivors_finished, (
        "no survivor witnessed any finished request in events.jsonl")
    uncovered = set(got) - survivors_finished - finished.get(victim.rid,
                                                             set())
    assert not uncovered, (
        f"uuids resolved but witnessed by no replica ledger: {uncovered}")
    print(f"proc fleet smoke OK: {N_ROWS} rows over {N_REPLICAS} OS "
          f"processes, child {victim.rid} SIGKILLed mid-decode"
          f"{' (serve.proc_kill)' if chaos else ''}, {requeued} "
          f"request(s) requeued, victim restarted (incarnation "
          f"{victim.incarnation}) and readmitted, every future resolved "
          f"exactly once, rows identical to the single-server run, "
          f"{len(survivors_finished)} finishes witnessed by survivors")


def _locksan_gate() -> None:
    """When TS_LOCKSAN=1 armed the sanitizer, the smoke doubles as the
    runtime validation of tslint's static lock-order graph: real
    acquisitions must have been observed and NONE may have inverted
    (an inversion would already have raised the typed
    LockOrderInversionError out of the failing path)."""
    from textsummarization_on_flink_tpu.obs import locksan

    if not locksan.active():
        return
    snap = locksan.snapshot()
    assert snap["acquisitions"] > 0, (
        "TS_LOCKSAN=1 but the smoke observed no sanitized acquisitions "
        "— the serve locks are not built through obs/locksan factories")
    assert snap["inversions"] == 0, snap
    print(f"locksan OK: {snap['acquisitions']} sanitized acquisitions, "
          f"0 inversions, {len(snap['order_edges'])} order edge(s), "
          f"{snap['unmodeled_edges']} unmodeled vs "
          f"{snap['static_graph'] or 'no static graph'}")


def main() -> None:
    transport = "inproc"
    for arg in sys.argv[1:]:
        if arg.startswith("--transport="):
            transport = arg.split("=", 1)[1]
        else:
            raise SystemExit(f"unknown argument {arg!r} "
                             f"(want --transport=inproc|proc)")
    if transport == "proc":
        run_proc()
    elif transport == "inproc":
        run_inproc()
    else:
        raise SystemExit(f"unknown transport {transport!r}")
    _locksan_gate()


if __name__ == "__main__":
    from textsummarization_on_flink_tpu.utils import (
        set_default_compile_cache,
    )

    set_default_compile_cache()
    main()
