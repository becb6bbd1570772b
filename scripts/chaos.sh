#!/usr/bin/env bash
# Chaos harness (RESILIENCE.md): drive every recovery path under
# deterministic fault injection.
#
#   scripts/chaos.sh          # chaos/resilience/bridge suites + TS_FAULTS sweeps
#
# Two layers:
#   1. the pytest chaos suite — each test pins its own fault plan
#      (seeded, via HParams(faults=...) or faultinject.use_plan), so the
#      exact same call indices fail on every run;
#   2. TS_FAULTS sweeps — the PROCESS-WIDE env arming path, exercised by
#      small end-to-end smokes per injection point (train divergence
#      recovery, source reconnect, checkpoint fallback, etl worker
#      restarts), asserting recovery through the resilience/* counters.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="$PWD"
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"

echo "== chaos + resilience + bridge suites (pinned per-test fault plans)"
python -m pytest tests/test_chaos.py tests/test_resilience.py \
  tests/test_bridge.py -q -p no:cacheprovider

echo
echo "== TS_FAULTS sweep: train.step_nan (divergence recovery end-to-end)"
TS_FAULTS="train.step_nan:1.0:7:3" python - <<'PY'
import numpy as np
from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.batching import Batch, SummaryExample
from textsummarization_on_flink_tpu.data.vocab import Vocab
from textsummarization_on_flink_tpu.train import trainer as trainer_lib
import tempfile

hps = HParams(batch_size=2, max_enc_steps=6, max_dec_steps=5, min_dec_steps=1,
              hidden_dim=4, emb_dim=3, max_oov_buckets=2, vocab_size=0,
              nan_skip_steps=2, nan_max_rollbacks=1,
              log_root=tempfile.mkdtemp(), exp_name="chaos")
vocab = Vocab(words=["a", "b", "c", "d", "e", "f", "."])
exs = [SummaryExample.build("a b c d", ["b c ."], vocab, hps),
       SummaryExample.build("c d e f", ["d e ."], vocab, hps)]
batch = Batch(exs, hps, vocab)

class FixedBatcher:
    n = 30
    def next_batch(self):
        if self.n <= 0:
            return None
        self.n -= 1
        return batch

trainer = trainer_lib.Trainer(hps, vocab.size(), FixedBatcher())
state = trainer.train(num_steps=6)
assert int(np.asarray(state.step)) == 6, "training did not complete"
skips = obs.counter("resilience/train/nan_skips_total").value
rollbacks = obs.counter("resilience/train/rollbacks_total").value
assert (skips, rollbacks) == (2, 1), (skips, rollbacks)
print(f"train.step_nan OK: {int(skips)} skips, {int(rollbacks)} rollback, "
      f"resumed to step 6 with no manual intervention")
PY

echo
echo "== TS_FAULTS sweep: io.read (source reconnect, exactly-once)"
TS_FAULTS="io.read:1.0:0:2" python - <<'PY'
import socketserver, threading
from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.pipeline import io as io_lib
from textsummarization_on_flink_tpu.resilience import faultinject

lines = [io_lib.Message(f"u{i}", f"art {i}", "", "r").to_json()
         for i in range(5)]

class H(socketserver.StreamRequestHandler):
    def handle(self):
        try:
            for line in lines:
                self.wfile.write((line + "\n").encode())
        except (BrokenPipeError, ConnectionResetError):
            pass

srv = socketserver.ThreadingTCPServer(("127.0.0.1", 0), H)
srv.daemon_threads = True
threading.Thread(target=srv.serve_forever, daemon=True).start()
src = io_lib.ResilientSource(
    lambda: io_lib.SocketSource("127.0.0.1", srv.server_address[1],
                                max_count=5),
    max_reconnects=4, seed=0, sleep=lambda d: None)
rows = list(src.rows())
srv.shutdown(); srv.server_close()
assert [r[0] for r in rows] == [f"u{i}" for i in range(5)], rows
fires = faultinject.plan().stats()["io.read"]["fires"]
reconnects = obs.counter("resilience/io_reconnects_total").value
assert fires == 2 and reconnects == 2, (fires, reconnects)
print(f"io.read OK: {fires} injected faults, {int(reconnects)} reconnects, "
      f"5 rows delivered exactly once")
PY

echo
echo "== TS_FAULTS sweep: ckpt.load (corruption fallback chain)"
TS_FAULTS="ckpt.load:1.0:0:1" python - <<'PY'
import tempfile
import numpy as np
from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.checkpoint import checkpointer as ckpt_lib
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.train import trainer as trainer_lib

hps = HParams(batch_size=2, max_enc_steps=6, max_dec_steps=5, min_dec_steps=1,
              hidden_dim=4, emb_dim=3, max_oov_buckets=2, vocab_size=0)
d = tempfile.mkdtemp()
ck = ckpt_lib.Checkpointer(d, hps=hps)
s1 = trainer_lib.init_train_state(hps, vsize=12, seed=0)
ck.save(s1)
ck.save(s1._replace(step=s1.step + 5))
restored = ck.restore()  # newest load fails (injected) -> next-older serves
assert restored is not None
assert int(np.asarray(restored.step)) == int(np.asarray(s1.step))
fallbacks = obs.counter("resilience/ckpt_fallbacks_total").value
assert fallbacks == 1, fallbacks
print("ckpt.load OK: corrupt-latest fell back to the next-older checkpoint")
PY

echo
echo "== TS_FAULTS sweep: etl.worker (bounded restart budget)"
TS_FAULTS="etl.worker:1.0:0:2" python - <<'PY'
from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.batcher import Batcher
from textsummarization_on_flink_tpu.data.vocab import Vocab

hps = HParams(batch_size=2, max_enc_steps=6, max_dec_steps=5, min_dec_steps=1,
              hidden_dim=4, emb_dim=3, max_oov_buckets=2, vocab_size=0)
vocab = Vocab(words=["the", "cat", "sat", "on", "mat", "."])
b = Batcher("", vocab, hps, single_pass=True,
            example_source=lambda: iter(
                [("the cat sat", "<s> the cat . </s>")] * 4),
            max_worker_restarts=3)
n = 0
while b.next_batch() is not None:
    n += 1
restarts = obs.counter("resilience/etl_worker_restarts_total").value
assert n == 2 and restarts == 2, (n, restarts)
print(f"etl.worker OK: {int(restarts)} crash restarts, data still flowed")
PY

echo
echo "== TS_FAULTS sweep: serve.replica_kill (fleet failover, exactly-once)"
TS_FAULTS="serve.replica_kill:1.0:0:1" python - <<'PY'
from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.vocab import Vocab
from textsummarization_on_flink_tpu.decode.decoder import DecodedResult
from textsummarization_on_flink_tpu.obs import Registry
from textsummarization_on_flink_tpu.resilience import faultinject
from textsummarization_on_flink_tpu.serve.batcher import NoArena
from textsummarization_on_flink_tpu.serve.fleet import FleetRouter
from textsummarization_on_flink_tpu.serve.server import ServingServer

class NullDecoder:
    def maybe_reload_checkpoint(self, last):
        return last

class SimEngine(NoArena):
    """3-chunk-per-request slot engine (jax-free): enough residency for
    the injected kill to land mid-decode."""
    def __init__(self, slots=2):
        self.slots, self._rem = slots, [0] * slots
        self._act = [False] * slots
    def pack(self, idx, ex):
        self._act[idx], self._rem[idx] = True, 3
    def step(self):
        fin = []
        for i in range(self.slots):
            if self._act[i]:
                self._rem[i] -= 1
                if self._rem[i] <= 0:
                    fin.append(i)
        return fin
    def unpack(self, idx, ex):
        self._act[idx] = False
        return DecodedResult(uuid=ex.uuid, article=ex.original_article,
                             decoded_words=["ok", "."],
                             reference=ex.reference, abstract_sents=[])
    def release(self, idx):
        self._act[idx] = False

vocab = Vocab(words=["w"])
hps = HParams(mode="decode", batch_size=2, vocab_size=vocab.size(),
              max_enc_steps=8, max_dec_steps=6, beam_size=2,
              min_dec_steps=1, max_oov_buckets=4, serve_max_queue=64,
              serve_mode="continuous", serve_slots=2, serve_refill_chunk=1,
              serve_replicas=3)
servers = [ServingServer(hps, vocab, decoder=NullDecoder(),
                         engine=SimEngine(), registry=Registry())
           for _ in range(3)]
router = FleetRouter(servers, hps)  # picks up the TS_FAULTS process plan
futs = [router.submit("w w w .", uuid=f"u{i}") for i in range(12)]
rounds = 0
while not all(f.done() for f in futs):
    rounds += 1
    assert rounds < 500, "fleet did not drain"
    router.tick()  # the armed serve.replica_kill fires on the first tick
    for h in router.replicas():
        if not h.killed:
            h.server.tick_once(poll=0.0)
results = [f.result(timeout=1) for f in futs]
assert [r.uuid for r in results] == [f"u{i}" for i in range(12)]
router.stop()
reg = obs.registry()
fires = faultinject.plan().stats()["serve.replica_kill"]["fires"]
kills = int(reg.counter("serve/replica_kills_total").value)
requeued = int(reg.counter("serve/requeued_total").value)
assert fires == 1 and kills == 1, (fires, kills)
assert requeued >= 1, requeued
assert sum(h.killed for h in router.replicas()) == 1
print(f"serve.replica_kill OK: 1 injected replica death, {requeued} "
      f"request(s) requeued on survivors, 12 futures resolved exactly once")
PY

echo
echo "== TS_FAULTS sweep: serve.proc_kill (OS-process fleet, SIGKILL failover)"
# the ISSUE-17 process boundary end to end: 3 supervised child
# processes behind the socket transport; the armed point makes the
# supervision thread SIGKILL the most-loaded live pid mid-decode, and
# the smoke asserts exactly-once + row parity + typed requeues on
# survivors + the victim restarted and readmitted through the rotation
# breaker's half-open probe (full contract in scripts/fleet_smoke.py)
# TS_LOCKSAN arms the runtime lock-order sanitizer on the sweep: the
# kill/requeue path is the richest lock interleaving the repo has, so
# it doubles as the inversion gate (obs/locksan; zero inversions)
TS_LOCKSAN=1 TS_FAULTS="serve.proc_kill:1.0:0:1" python scripts/fleet_smoke.py \
  --transport=proc

echo
echo "== TS_FAULTS sweep: serve.cache_fault (front door degrades to miss)"
TS_FAULTS="serve.cache_fault:1.0:0" python - <<'PY'
from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.vocab import Vocab
from textsummarization_on_flink_tpu.decode.decoder import DecodedResult
from textsummarization_on_flink_tpu.resilience import faultinject
from textsummarization_on_flink_tpu.serve.server import ServingServer

class EchoDecoder:
    """Content-deterministic stub: the cache CONTRACT (never a wrong
    summary, never a hung future) is host-side, no device needed."""
    def should_degrade(self, deadline):
        return False
    def decode_batch(self, batch, deadline=None, tier=None):
        return [DecodedResult(
                    uuid=batch.uuids[b], article=batch.original_articles[b],
                    decoded_words=batch.original_articles[b].split()[:3],
                    reference=batch.references[b], abstract_sents=[])
                for b in range(len(batch.uuids)) if batch.real_mask[b]]
    def maybe_reload_checkpoint(self, last):
        return last

vocab = Vocab(words=["the", "cat", "sat", "."])
hps = HParams(mode="decode", batch_size=2, vocab_size=vocab.size(),
              max_enc_steps=8, max_dec_steps=4, beam_size=2,
              min_dec_steps=1, max_oov_buckets=4, serve_max_queue=16,
              serve_cache_entries=8)
with ServingServer(hps, vocab, decoder=EchoDecoder()) as server:
    r1 = server.submit("the cat sat .", uuid="u1").result(timeout=30)
    r2 = server.submit("the cat sat .", uuid="u2").result(timeout=30)
reg = obs.registry()
fires = faultinject.plan().stats()["serve.cache_fault"]["fires"]
hits = int(reg.counter("serve/cache_hits_total").value)
errors = int(reg.counter("serve/cache_errors_total").value)
decodes = int(reg.counter("serve/completed_total").value)
assert r1.summary == r2.summary, (r1.summary, r2.summary)
assert hits == 0 and decodes == 2, (hits, decodes)
assert fires >= 2 and errors >= 2, (fires, errors)
print(f"serve.cache_fault OK: {fires} injected cache faults degraded to "
      f"miss-and-decode ({decodes} decodes, 0 hits), summaries identical, "
      f"every future resolved")
PY

echo
echo "== TS_FAULTS sweep: serve.arena_full (admission by free pages requeues, never rejects)"
TS_FAULTS="serve.arena_full:1.0:0:2" python - <<'PY'
import glob
import tempfile
from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.vocab import Vocab
from textsummarization_on_flink_tpu.decode.decoder import DecodedResult
from textsummarization_on_flink_tpu.obs import flightrec
from textsummarization_on_flink_tpu.resilience import faultinject
from textsummarization_on_flink_tpu.serve.server import ServingServer

class NullDecoder:
    def maybe_reload_checkpoint(self, last):
        return last

class PagedSimEngine:
    """Jax-free slot engine with a page arena (ISSUE 20): 4 pages over 2
    slots, 2 decode chunks per request — the REAL ContinuousBatcher
    does the page-gated admission; the armed serve.arena_full point
    lands the allocation failure inside pack."""
    def __init__(self, slots=2, pages=4, page_words=4):
        self.slots, self._cap = slots, pages
        self._free = list(range(pages))
        self._page_words = page_words
        self._held = [[] for _ in range(slots)]
        self._rem = [0] * slots
    def prefill(self, ex):
        return ex
    def pages_needed(self, ex):
        words = len(ex.original_article.split())
        return max(1, -(-words // self._page_words))
    def free_pages(self):
        return len(self._free)
    def arena_stats(self):
        in_use = self._cap - len(self._free)
        return {"capacity": self._cap, "free": len(self._free),
                "in_use": in_use, "fill": in_use / self._cap}
    def pack(self, idx, ex):
        self._held[idx] = [self._free.pop()
                           for _ in range(self.pages_needed(ex))]
        self._rem[idx] = 2
    def step(self):
        fin = []
        for i in range(self.slots):
            if self._rem[i] > 0:
                self._rem[i] -= 1
                if self._rem[i] == 0:
                    fin.append(i)
        return fin
    def _release_pages(self, idx):
        self._free.extend(self._held[idx])
        self._held[idx] = []
    def unpack(self, idx, ex):
        self._release_pages(idx)
        return DecodedResult(uuid=ex.uuid, article=ex.original_article,
                             decoded_words=["ok", "."],
                             reference=ex.reference, abstract_sents=[])
    def release(self, idx):
        self._release_pages(idx)
        self._rem[idx] = 0

vocab = Vocab(words=["w"])
hps = HParams(mode="decode", batch_size=2, vocab_size=vocab.size(),
              max_enc_steps=8, max_dec_steps=6, beam_size=2,
              min_dec_steps=1, max_oov_buckets=4, serve_max_queue=64,
              serve_mode="continuous", serve_slots=2, serve_refill_chunk=1)
ring = tempfile.mkdtemp()
reg = obs.registry()
flightrec.install_flight_recorder(reg, ring)
engine = PagedSimEngine()
with ServingServer(hps, vocab, decoder=NullDecoder(),
                   engine=engine) as server:
    futs = [server.submit("w w w w w w .", uuid=f"u{i}") for i in range(8)]
    results = [f.result(timeout=60) for f in futs]
assert [r.uuid for r in results] == [f"u{i}" for i in range(8)]
fires = faultinject.plan().stats()["serve.arena_full"]["fires"]
fails = int(reg.counter("serve/arena_alloc_failures_total").value)
assert fires == 2 and fails >= 2, (fires, fails)
assert engine.arena_stats()["in_use"] == 0, engine.arena_stats()
dumps = glob.glob(ring + "/flight_arena_exhausted*.jsonl")
assert len(dumps) == 1, dumps  # rising edge only: ONE dump per episode
print(f"serve.arena_full OK: {fires} injected allocation failures "
      f"requeued (never rejected), 8 futures resolved exactly once, "
      f"arena drained to 0, 1 flight dump ({dumps[0].rsplit('/', 1)[-1]})")
PY

echo
echo "chaos OK"
