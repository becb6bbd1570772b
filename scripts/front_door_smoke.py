"""Front-door smoke (ISSUE 14): a duplicate-heavy request mix through
a real tiny model with coalescing + the summary cache armed — the
no-hardware proof that the production front door works end to end:

  * a burst of identical articles submitted together COALESCES onto one
    decode (``serve/coalesced_total`` > 0) and every future resolves
    exactly once with its own uuid;
  * a second pass over the same articles is served from the CACHE
    (``serve/cache_hits_total``; zero new decodes) with each hit row
    byte-identical to its original decode — the pointer-generator's
    deterministic tiers are what make the reuse exact;
  * a third pass at a DIFFERENT tier misses (the tier is part of the
    key) and decodes fresh.

The committed scheduling claims (zipf decode ratio, p99, tenant
isolation, fleet composition) live in SERVE_SLO.json "front_door" and
are enforced by tests/test_serve_slo.py over virtual time; this smoke
proves the THREADED path on a real model.  Wired into
scripts/repro.sh.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import tempfile  # noqa: E402

from textsummarization_on_flink_tpu import obs  # noqa: E402
from textsummarization_on_flink_tpu.config import HParams  # noqa: E402
from textsummarization_on_flink_tpu.data.vocab import Vocab  # noqa: E402
from textsummarization_on_flink_tpu.serve.server import (  # noqa: E402
    ServingServer,
)
from textsummarization_on_flink_tpu.train import trainer  # noqa: E402


def main() -> None:
    # duplicate-heavy mix: 12 requests over 3 DISTINCT articles
    distinct = ["article 0 .",
                "article 1 " + ". article " * 5 + ".",
                "article 2 article 0 ."]
    requests = [(f"uuid-{i}", distinct[i % 3]) for i in range(12)]
    vocab = Vocab(words=["article", "reference", ".", "0", "1", "2"])
    hps = HParams(mode="decode", batch_size=2, hidden_dim=16, emb_dim=8,
                  vocab_size=vocab.size(), max_enc_steps=16,
                  max_dec_steps=6, beam_size=2, min_dec_steps=1,
                  max_oov_buckets=4, serve_max_wait_ms=50.0,
                  serve_max_queue=64, serve_buckets="8,16",
                  serve_coalesce=True, serve_cache_entries=32)
    params = trainer.init_train_state(hps, vocab.size(), seed=0).params
    reg = obs.registry()
    server = ServingServer(
        hps, vocab, params=params,
        decode_root=tempfile.mkdtemp(prefix="front_door_smoke_"))
    with server:
        # pass 1: the burst — duplicates in flight together coalesce
        futs = [server.submit(a, uuid=u) for u, a in requests]
        rows1 = {u: f.result(timeout=600).as_row() for (u, _), f
                 in zip(requests, futs)}
        decodes1 = int(reg.counter("serve/completed_total").value)
        coalesced = int(reg.counter("serve/coalesced_total").value)
        assert sorted(rows1) == sorted(u for u, _ in requests)
        assert coalesced > 0, (
            "no submits coalesced — the burst never shared a decode")
        assert decodes1 + coalesced + int(
            reg.counter("serve/cache_hits_total").value) == len(requests)
        # same article => byte-identical summary, whatever the uuid
        by_article = {}
        for (u, a), _ in zip(requests, futs):
            by_article.setdefault(a, set()).add(rows1[u][2])
        assert all(len(s) == 1 for s in by_article.values()), by_article

        # pass 2: the cache — zero new decodes, rows byte-identical to
        # the original decode (the row-parity pin)
        futs2 = [server.submit(a, uuid=u + "-again") for u, a in requests]
        rows2 = [f.result(timeout=600).as_row() for f in futs2]
        decodes2 = int(reg.counter("serve/completed_total").value)
        hits = int(reg.counter("serve/cache_hits_total").value)
        assert decodes2 == decodes1, (
            f"warm pass decoded ({decodes2 - decodes1} new decodes)")
        assert hits >= len(requests), hits
        for (u, a), row in zip(requests, rows2):
            assert row[0] == u + "-again"
            assert row[2] == rows1[u][2], (
                f"cache hit row for {a!r} drifted from its original "
                f"decode")

        # pass 3: a different tier is a different key — fresh decodes
        fut3 = server.submit(distinct[0], uuid="greedy-0", tier="greedy")
        fut3.result(timeout=600)
        assert int(reg.counter("serve/completed_total").value) \
            == decodes2 + 1, "a new tier must miss and decode"

    age = reg.histogram("serve/cache_entry_age_seconds")
    print(f"front-door smoke OK: {len(requests)} duplicate-heavy "
          f"requests -> {decodes1} decodes ({coalesced} coalesced), "
          f"warm pass {hits} cache hits / 0 decodes with byte-identical "
          f"rows, tier axis missed as designed "
          f"(entries {int(reg.gauge('serve/cache_entries').value)}, "
          f"mean hit age {age.mean * 1000:.1f} ms)")


if __name__ == "__main__":
    from textsummarization_on_flink_tpu.utils import (
        set_default_compile_cache,
    )

    set_default_compile_cache()
    main()
