"""Live-plane smoke (ISSUE 9): start the obs HTTP exposition server,
drive a short continuous-serve run against the real tiny model while
scraping /metrics and /healthz, and assert the scrape is byte-identical
to ``obs.render_text()`` once the run quiesces.  Also proves the
request-trace path end to end: the run writes a unified events.jsonl
and ``scripts/trace_summary.py --request`` reconstructs one uuid's
timeline from it.

Fleet leg (ISSUE 15): a 2-replica FleetRouter with per-replica
registries is scraped on ``/fleet/metrics`` DURING a real run; once
quiesced, the merged ``serve_completed_total`` must equal the sum of
the two per-replica scrapes, and one ``/exemplars`` trace_id must
resolve to a reconstructable cross-replica timeline through
``trace_summary.py --request``.  Wired into scripts/repro.sh.
"""

import json
import os
import subprocess
import sys
import tempfile
import urllib.error
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from textsummarization_on_flink_tpu import obs  # noqa: E402
from textsummarization_on_flink_tpu.config import HParams  # noqa: E402
from textsummarization_on_flink_tpu.data.vocab import Vocab  # noqa: E402
from textsummarization_on_flink_tpu.serve.fleet import (  # noqa: E402
    FleetRouter,
)
from textsummarization_on_flink_tpu.serve.server import (  # noqa: E402
    ServingServer,
)
from textsummarization_on_flink_tpu.train import trainer  # noqa: E402


def get(port: int, route: str, accept: str = ""):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{route}",
        headers={"Accept": accept} if accept else {})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, resp.read()


def scrape_value(body: bytes, name: str) -> float:
    """The UNLABELED series value of `name` in a text exposition."""
    for line in body.decode("utf-8").splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    raise AssertionError(f"{name} not in scrape")


def run_fleet_leg(hps, vocab, params) -> None:
    """The ISSUE 15 fleet leg: 2 replicas, own registries, one router;
    merged /fleet scrape == sum of per-replica scrapes, exemplar ->
    timeline."""
    events_dir = tempfile.mkdtemp(prefix="obs_http_smoke_fleet_")
    router_reg = obs.Registry()
    rep_regs = [obs.Registry(), obs.Registry()]
    sink = obs.install_event_sink(events_dir, flush_secs=0.1,
                                  reg=router_reg)
    replicas = [
        ServingServer(hps, vocab, params=params, registry=rep_regs[i],
                      decode_root=tempfile.mkdtemp(
                          prefix=f"obs_http_smoke_rep{i}_"))
        for i in range(2)]
    router = FleetRouter(replicas, hps, registry=router_reg)
    fleet_srv = obs.serve_http(0, router_reg)
    rep_srvs = [obs.serve_http(0, r) for r in rep_regs]
    try:
        with router:
            futs = [router.submit(f"article {i} .", uuid=f"fleet-{i}")
                    for i in range(8)]
            # the fleet plane must answer WHILE replicas decode
            status, live = get(fleet_srv.port, "/fleet/metrics")
            assert status == 200 and b"# TYPE" in live
            for f in futs:
                f.result(timeout=600)
            # quiesced (every future resolved, fleet still up): merged
            # counter == sum of the per-replica scrapes
            status, merged = get(fleet_srv.port, "/fleet/metrics")
            assert status == 200
            total = scrape_value(merged, "serve_completed_total")
            per_rep = []
            for srv in rep_srvs:
                _, body = get(srv.port, "/metrics")
                per_rep.append(scrape_value(body,
                                            "serve_completed_total"))
            assert total == sum(per_rep) == 8.0, (total, per_rep)
            assert all(v > 0 for v in per_rep), (
                f"least-loaded routing left a replica idle: {per_rep}")
            _, snap = get(fleet_srv.port, "/fleet/snapshot")
            fleet_snap = json.loads(snap)
            assert fleet_snap["replicas"] == ["router", "r0", "r1"], \
                fleet_snap["replicas"]
            assert fleet_snap["metrics"]["serve/completed_total"][
                "value"] == 8.0
            assert set(fleet_snap["health"]) == {"r0", "r1"}, \
                fleet_snap["health"]
            _, alerts = get(fleet_srv.port, "/alerts")
            payload = json.loads(alerts)
            assert payload["installed"] and payload["status"] == "ok", \
                payload
        # a STOPPED fleet retires its source map: /fleet/* answers 404
        # rather than serving (and memory-pinning) a dead fleet
        try:
            get(fleet_srv.port, "/fleet/metrics")
            raise AssertionError("/fleet/metrics served a stopped fleet")
        except urllib.error.HTTPError as e:
            assert e.code == 404
        # one exemplar -> one reconstructable cross-replica timeline
        exemplar = None
        for srv in rep_srvs:
            _, body = get(srv.port, "/exemplars")
            for row in json.loads(body):
                if row["metric"].startswith("serve/e2e_latency_seconds"):
                    exemplar = row
                    break
            if exemplar:
                break
        assert exemplar is not None, "no e2e exemplar on either replica"
        out = subprocess.run(
            [sys.executable, os.path.join(os.path.dirname(__file__),
                                          "trace_summary.py"),
             events_dir, "--request", exemplar["trace_id"], "--json"],
            capture_output=True, text=True, check=True)
        tl = json.loads(out.stdout)
        stages = {e["event"] for e in tl["events"]}
        assert {"enqueue", "route", "resolve"} <= stages, stages
        assert tl["uuid"].startswith("fleet-"), tl["uuid"]
        replicas_seen = {e["replica"] for e in tl["events"]
                         if "replica" in e}
        assert replicas_seen, "no replica-tagged lifecycle events"
        print(f"obs http fleet smoke OK: merged {total:g} == "
              f"{'+'.join(f'{v:g}' for v in per_rep)}, exemplar "
              f"{exemplar['trace_id']} -> {tl['uuid']} "
              f"({sorted(stages)}, replicas {sorted(replicas_seen)})")
    finally:
        fleet_srv.close()
        for srv in rep_srvs:
            srv.close()
        sink.close()


def main() -> None:
    vocab = Vocab(words=["article", "reference", ".", "0", "1", "2", "3",
                         "4", "5", "6", "7"])
    hps = HParams(mode="decode", batch_size=2, hidden_dim=16, emb_dim=8,
                  vocab_size=vocab.size(), max_enc_steps=16, max_dec_steps=6,
                  beam_size=2, min_dec_steps=1, max_oov_buckets=4,
                  serve_mode="continuous", serve_slots=2,
                  serve_refill_chunk=2, serve_max_queue=32)
    params = trainer.init_train_state(hps, vocab.size(), seed=0).params

    # TS_SMOKE_OUT (ISSUE 16): a caller-named events dir, so repro.sh
    # can hand the run's events.jsonl straight to perf_report.py
    events_dir = os.environ.get("TS_SMOKE_OUT") or tempfile.mkdtemp(
        prefix="obs_http_smoke_")
    os.makedirs(events_dir, exist_ok=True)
    sink = obs.install_event_sink(events_dir, flush_secs=0.1)
    srv = obs.serve_http(0)  # ephemeral localhost port
    try:
        server = ServingServer(
            hps, vocab, params=params,
            decode_root=tempfile.mkdtemp(prefix="obs_http_smoke_dec_"))
        with server:
            futs = [server.submit(f"article {i} .", uuid=f"uuid-{i}")
                    for i in range(8)]
            # scrape DURING the loaded run: both endpoints must answer
            # while the dispatch thread is working
            status, live_metrics = get(srv.port, "/metrics")
            assert status == 200 and b"# TYPE" in live_metrics
            status, health = get(srv.port, "/healthz")
            payload = json.loads(health)
            assert payload["status"] in ("ok", "degraded"), payload
            assert "serve/dispatch" in payload["components"], payload
            for f in futs:
                f.result(timeout=600)
            # performance attribution plane (ISSUE 16): /profile must
            # answer on the live server with a non-empty phase table
            # and the committed compile warm set — 4 decode kernels
            # (init/pack/step/unpack) + one prefill per bucket USED
            status, prof_body = get(srv.port, "/profile")
            assert status == 200
            prof = json.loads(prof_body)
            assert prof["installed"], prof
            phase_names = {p["phase"] for p in prof["phases"]}
            assert {"serve/prefill", "serve/dispatch",
                    "serve/harvest"} <= phase_names, phase_names
            ledger = prof["compile_ledger"]
            sites = ledger["sites"]
            prefills = sites.get("decode/prefill_jit",
                                 {"compiles": 0})["compiles"]
            assert prefills >= 1, sites
            decode_kernels = sum(
                sites.get(k, {"compiles": 0})["compiles"]
                for k in ("decode/init_slots_jit", "decode/pack_slot_jit",
                          "decode/step_slots_jit",
                          "decode/unpack_slot_jit"))
            assert decode_kernels == 4, sites
            assert ledger["warm_set"] == 4 + prefills, ledger
            assert ledger["storm"] is None, ledger
            # the profiler's cached storm/divergence state rides the
            # /alerts scrape under the "profile" key
            _, alerts_body = get(srv.port, "/alerts")
            alerts = json.loads(alerts_body)
            assert alerts["profile"]["installed"], alerts
            assert alerts["profile"]["compile_storm"] is None, alerts
        # quiesced: an OpenMetrics-negotiated scrape must be
        # byte-identical to the in-process exposition (same counter
        # set, same values, exemplar annotations included); a plain
        # Prometheus-0.0.4 scrape must carry NO exemplar annotations
        # (a 0.0.4 parser would reject them)
        status, body = get(srv.port, "/metrics",
                           accept="application/openmetrics-text")
        assert status == 200
        rendered = obs.render_text(openmetrics=True).encode("utf-8")
        assert body == rendered, (
            f"scrape ({len(body)}B) != render_text ({len(rendered)}B)")
        _, plain = get(srv.port, "/metrics")
        assert b"trace_id" not in plain
        status, health = get(srv.port, "/healthz")
        payload = json.loads(health)
        # the stopped server RETIRED its beat — a finished component
        # must not pin /healthz at degraded
        assert "serve/dispatch" not in payload["components"], payload
        status, snap = get(srv.port, "/snapshot")
        snapshot = json.loads(snap)
        assert snapshot.get("serve/completed_total", {}).get("value") == 8.0
    finally:
        srv.close()
        sink.close()

    # one uuid's timeline back out of the unified events.jsonl
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "trace_summary.py"),
         events_dir, "--request", "uuid-3", "--json"],
        capture_output=True, text=True, check=True)
    tl = json.loads(out.stdout)
    stages = {e["event"] for e in tl["events"]}
    assert {"enqueue", "admit", "slot", "finish", "resolve"} <= stages, stages
    assert tl["phases"].get("total_ms") is not None, tl["phases"]
    print(f"obs http smoke OK: scrape == render_text "
          f"({len(body)} bytes), healthz {payload['status']} "
          f"({', '.join(sorted(payload['components']))}), uuid-3 timeline "
          f"{sorted(stages)} over {tl['phases']['total_ms']:.1f} ms, "
          f"/profile warm set {ledger['warm_set']} "
          f"(4 decode + {prefills} prefill), coverage "
          f"{prof['coverage']:.3f}")

    run_fleet_leg(hps, vocab, params)


if __name__ == "__main__":
    from textsummarization_on_flink_tpu.utils import (
        set_default_compile_cache,
    )

    set_default_compile_cache()
    main()
