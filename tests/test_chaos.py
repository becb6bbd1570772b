"""Chaos suite: deterministic fault injection drives every recovery path
(RESILIENCE.md; ISSUE 2 acceptance criteria).

Each test arms named injection points (``TS_FAULTS`` syntax via HParams
or ``faultinject.use_plan``) with pinned seeds, so the same call indices
fail on every run, and asserts the recovery *sequence* — skips, rollbacks,
reconnects, fallbacks, restarts — through the ``resilience/*`` obs
counters, not just the final output.

Run explicitly with ``-m chaos`` (scripts/chaos.sh sweeps TS_FAULTS on
top); the whole file is also part of the default suite — every test is
deterministic and CPU-fast.
"""

import json
import socketserver
import threading
import time

import numpy as np
import pytest

from textsummarization_on_flink_tpu.serve.batcher import NoArena
from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.checkpoint import checkpointer as ckpt_lib
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.batcher import Batcher
from textsummarization_on_flink_tpu.data.batching import Batch, SummaryExample
from textsummarization_on_flink_tpu.data.vocab import Vocab
from textsummarization_on_flink_tpu.decode import decoder as dec_lib
from textsummarization_on_flink_tpu.obs import Registry
from textsummarization_on_flink_tpu.pipeline import io as io_lib
from textsummarization_on_flink_tpu.resilience import (
    CheckpointCorruptError,
    CircuitBreaker,
    Deadline,
    FaultPlan,
    FaultSpec,
    RetriesExhaustedError,
    StreamIdleError,
    WorkerCrashError,
    faultinject,
)
from textsummarization_on_flink_tpu.train import trainer as trainer_lib

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _isolated_obs_and_faults():
    """Every chaos test gets a fresh obs registry (counter assertions)
    and leaves no fault plan cached behind."""
    with obs.use_registry(Registry()) as reg:
        yield reg
    faultinject.set_default_plan(None)


# -- trainer: divergence recovery (acceptance criterion 1) -----------------

def hps_tiny(**kw):
    base = dict(batch_size=2, max_enc_steps=6, max_dec_steps=5,
                min_dec_steps=1, hidden_dim=4, emb_dim=3, max_oov_buckets=2,
                vocab_size=0, lr=0.15, adagrad_init_acc=0.1,
                max_grad_norm=2.0)
    base.update(kw)
    return HParams(**base)


class FixedBatcher:
    def __init__(self, batch, n):
        self.batch, self.n = batch, n

    def next_batch(self):
        if self.n <= 0:
            return None
        self.n -= 1
        return self.batch


def make_batch(hps, vocab):
    exs = [SummaryExample.build("a b c d", ["b c ."], vocab, hps),
           SummaryExample.build("c d e f", ["d e ."], vocab, hps)]
    return Batch(exs, hps, vocab)


class TestTrainDivergenceRecovery:
    def test_injected_nan_skips_then_rolls_back_then_completes(
            self, tmp_path, _isolated_obs_and_faults):
        """End-to-end: with ``train.step_nan`` injected 3 times at p=1.0,
        the trainer burns its 2-skip budget, rolls back once with an LR
        cut, and training then resumes to completion without manual
        intervention (the acceptance sequence)."""
        reg = _isolated_obs_and_faults
        hps = hps_tiny(log_root=str(tmp_path), exp_name="t",
                       nan_skip_steps=2, nan_max_rollbacks=1,
                       faults="train.step_nan:1.0:7:3")
        vocab = Vocab(words=["a", "b", "c", "d", "e", "f", "."])
        batch = make_batch(hps, vocab)
        ck = ckpt_lib.Checkpointer(str(tmp_path / "ckpt"), hps=hps)
        trainer = trainer_lib.Trainer(hps, vocab.size(),
                                      FixedBatcher(batch, 30),
                                      checkpointer=ck,
                                      checkpoint_secs=1e9)
        state = trainer.train(num_steps=6)
        # training COMPLETED despite 3 injected divergences
        assert int(np.asarray(state.step)) == 6
        assert reg.counter("resilience/train/nan_skips_total").value == 2
        assert reg.counter("resilience/train/rollbacks_total").value == 1
        assert reg.counter("train/nan_watchdog_total").value == 3
        # one rollback cut the LR by nan_lr_cut (default 0.5)
        assert reg.gauge("resilience/train/lr_scale").value == 0.5
        assert trainer._faults.stats()["train.step_nan"]["fires"] == 3

    def test_budgets_exhausted_raises_nan_loss_error(self, tmp_path):
        hps = hps_tiny(log_root=str(tmp_path), exp_name="t",
                       nan_skip_steps=1, nan_max_rollbacks=1,
                       faults="train.step_nan:1.0:7")  # unbounded fires
        vocab = Vocab(words=["a", "b", "c", "d", "e", "f", "."])
        batch = make_batch(hps, vocab)
        trainer = trainer_lib.Trainer(hps, vocab.size(),
                                      FixedBatcher(batch, 30))
        with pytest.raises(trainer_lib.NanLossError, match="exhausted"):
            trainer.train(num_steps=6)

    def test_unarmed_injection_keeps_hard_abort(self, tmp_path):
        """Default HParams (both budgets 0): the reference's fail-fast
        watchdog contract survives — an injected divergence aborts."""
        hps = hps_tiny(log_root=str(tmp_path), exp_name="t",
                       faults="train.step_nan:1.0:7:1")
        vocab = Vocab(words=["a", "b", "c", "d", "e", "f", "."])
        batch = make_batch(hps, vocab)
        trainer = trainer_lib.Trainer(hps, vocab.size(),
                                      FixedBatcher(batch, 10))
        with pytest.raises(trainer_lib.NonFiniteLossError, match="injected"):
            trainer.train(num_steps=4)


# -- pipeline source: reconnect with backoff (acceptance criterion 2) ------

def _serve_lines(lines):
    """A TCP server that streams `lines` to every connection, forever
    (each reconnect replays from the start, like a re-polled topic)."""
    class Handler(socketserver.StreamRequestHandler):
        def handle(self):
            try:
                for line in lines:
                    self.wfile.write((line + "\n").encode())
            except (BrokenPipeError, ConnectionResetError):
                pass

    server = socketserver.ThreadingTCPServer(("127.0.0.1", 0), Handler)
    server.daemon_threads = True
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    return server, server.server_address[1]


class TestSourceReconnect:
    def test_injected_io_read_reconnects_and_delivers_exactly_once(
            self, _isolated_obs_and_faults):
        """Acceptance: with io.read faults injected, the source
        reconnects with backoff and every row reaches the consumer
        exactly once, all visible in resilience/* counters."""
        reg = _isolated_obs_and_faults
        lines = [io_lib.Message(f"u{i}", f"art {i}", "", "ref").to_json()
                 for i in range(5)]
        server, port = _serve_lines(lines)
        try:
            # p=1.0 max=2: the first two read attempts fail, the third
            # connection streams clean — same indices every run
            plan = FaultPlan([FaultSpec("io.read", 1.0, 0, 2)],
                             registry=reg)
            with faultinject.use_plan(plan):
                src = io_lib.ResilientSource(
                    lambda: io_lib.SocketSource("127.0.0.1", port,
                                                max_count=5),
                    max_reconnects=4, seed=0, sleep=lambda d: None)
                got = list(src.rows())
        finally:
            server.shutdown()
            server.server_close()
        assert [r[0] for r in got] == [f"u{i}" for i in range(5)]
        assert plan.stats()["io.read"]["fires"] == 2
        assert reg.counter("resilience/io_reconnects_total").value == 2
        assert reg.counter("resilience/fault/io.read").value == 2

    def test_replayed_rows_are_deduped(self, _isolated_obs_and_faults):
        """A peer that dies mid-stream and replays from the start on
        reconnect must not hand the consumer duplicates."""
        reg = _isolated_obs_and_faults
        rows = [(f"u{i}", f"art {i}", "", "r") for i in range(5)]
        calls = {"n": 0}

        class FlakySource(io_lib.Source):
            schema = io_lib.ARTICLE_INPUT_SCHEMA

            def rows(self):
                calls["n"] += 1
                if calls["n"] == 1:  # first connection dies after 3 rows
                    yield from rows[:3]
                    raise ConnectionResetError("peer died mid-stream")
                yield from rows  # replay from the start

        src = io_lib.ResilientSource(FlakySource, max_reconnects=2, seed=0,
                                     sleep=lambda d: None)
        got = list(src.rows())
        assert got == rows  # exactly once, in order
        assert reg.counter("resilience/io_dup_rows_total").value == 3
        assert reg.counter("resilience/io_reconnects_total").value == 1

    def test_dedup_window_bounds_memory(self, _isolated_obs_and_faults):
        """Dedup memory is a bounded LRU window, not an ever-growing
        set: keys inside the window still dedup, keys evicted from it
        are re-delivered (the documented tradeoff on endless streams),
        and every eviction is counted
        (``pipeline/dedup_evictions_total``, the ISSUE-13 satellite)."""
        reg = _isolated_obs_and_faults
        calls = {"n": 0}

        class FlakySource(io_lib.Source):
            schema = io_lib.ARTICLE_INPUT_SCHEMA

            def rows(self):
                calls["n"] += 1
                if calls["n"] == 1:
                    for i in range(3):
                        yield (f"u{i}", "a", "", "r")
                    raise ConnectionResetError("flap")
                yield ("u2", "a", "", "r")  # within the 2-key window: dup
                yield ("u0", "a", "", "r")  # evicted: re-delivered
                yield ("u3", "a", "", "r")

        src = io_lib.ResilientSource(
            FlakySource, max_reconnects=2, seed=0, dedup_window=2,
            schema=io_lib.ARTICLE_INPUT_SCHEMA, sleep=lambda d: None)
        keys = [r[0] for r in src.rows()]
        assert keys == ["u0", "u1", "u2", "u0", "u3"]
        assert reg.counter("pipeline/dedup_evictions_total").value == 3

    def test_dedup_lru_refresh_protects_replayed_keys(
            self, _isolated_obs_and_faults):
        """The LRU half of the ISSUE-13 satellite: a replayed key
        refreshes its recency, so a peer that replays the same prefix
        on every reconnect cannot age live keys out of the window (the
        FIFO window would have re-delivered u0 here — a duplicate
        leak)."""
        reg = _isolated_obs_and_faults
        calls = {"n": 0}

        class FlakySource(io_lib.Source):
            schema = io_lib.ARTICLE_INPUT_SCHEMA

            def rows(self):
                calls["n"] += 1
                if calls["n"] == 1:
                    yield ("u0", "a", "", "r")
                    yield ("u1", "a", "", "r")
                    raise ConnectionResetError("flap")
                yield ("u0", "a", "", "r")  # replayed: refreshes u0
                yield ("u2", "a", "", "r")  # evicts u1, NOT fresh u0
                yield ("u0", "a", "", "r")  # still inside the window
                yield ("u3", "a", "", "r")

        src = io_lib.ResilientSource(
            FlakySource, max_reconnects=2, seed=0, dedup_window=2,
            schema=io_lib.ARTICLE_INPUT_SCHEMA, sleep=lambda d: None)
        keys = [r[0] for r in src.rows()]
        assert keys == ["u0", "u1", "u2", "u3"]  # u0 never re-delivered
        assert reg.counter("resilience/io_dup_rows_total").value == 2
        assert reg.counter("pipeline/dedup_evictions_total").value == 2

    def test_reconnect_budget_exhausted_raises_typed(
            self, _isolated_obs_and_faults):
        reg = _isolated_obs_and_faults

        class DeadSource(io_lib.Source):
            schema = io_lib.ARTICLE_INPUT_SCHEMA

            def rows(self):
                raise ConnectionRefusedError("nobody home")
                yield  # pragma: no cover

        src = io_lib.ResilientSource(DeadSource, max_reconnects=2, seed=0,
                                     sleep=lambda d: None)
        with pytest.raises(RetriesExhaustedError) as ei:
            list(src.rows())
        assert isinstance(ei.value.__cause__, ConnectionRefusedError)
        assert reg.counter(
            "resilience/io.source/retry_exhausted_total").value == 1

    def test_socket_idle_timeout_raises_stream_idle_error(self):
        """Satellite 1: a silent (but connected) peer surfaces as a typed
        StreamIdleError instead of hanging the source forever."""
        hold = threading.Event()

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):
                self.wfile.write(
                    (io_lib.Message("u0", "art", "", "r").to_json()
                     + "\n").encode())
                self.wfile.flush()
                hold.wait(5)  # go silent without closing

        server = socketserver.ThreadingTCPServer(("127.0.0.1", 0), Handler)
        server.daemon_threads = True
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            src = io_lib.SocketSource("127.0.0.1", server.server_address[1],
                                      idle_timeout=0.3)
            it = src.rows()
            assert next(it)[0] == "u0"  # live data flows
            t0 = time.monotonic()
            with pytest.raises(StreamIdleError, match="no data"):
                next(it)
            assert time.monotonic() - t0 < 5.0  # bounded, not forever
        finally:
            hold.set()
            server.shutdown()
            server.server_close()


# -- sink: circuit breaker sheds instead of blocking -----------------------

class TestBreakerSink:
    def test_open_breaker_sheds_then_half_open_probe_recovers(
            self, _isolated_obs_and_faults):
        reg = _isolated_obs_and_faults
        clock = [0.0]
        breaker = CircuitBreaker(threshold=3, reset_secs=30.0,
                                 name="io.sink", clock=lambda: clock[0],
                                 registry=reg)
        inner = io_lib.CollectionSink()
        # io.write fails the first 3 protected writes, then heals
        plan = FaultPlan([FaultSpec("io.write", 1.0, 0, 3)], registry=reg)
        with faultinject.use_plan(plan):
            sink = io_lib.BreakerSink(inner, breaker=breaker)
            for i in range(5):
                sink.write((f"u{i}", "a", "s", "r"))
        # 3 failures tripped the breaker; writes 4 and 5 shed immediately
        assert breaker.state == CircuitBreaker.OPEN
        assert inner.rows == []
        assert reg.counter("resilience/sink_errors_total").value == 3
        assert reg.counter("resilience/sink_shed_total").value == 5
        # reset window elapses: the half-open probe write goes through
        # (the fault budget is spent) and the breaker re-closes
        clock[0] = 31.0
        sink.write(("u5", "a", "s", "r"))
        assert breaker.state == CircuitBreaker.CLOSED
        assert inner.rows == [("u5", "a", "s", "r")]
        sink.close()


# -- checkpointer: checksum manifests + corruption fallback ----------------

def tiny_state(hps, seed=0):
    return trainer_lib.init_train_state(
        hps, vsize=12, seed=seed)


class TestCheckpointCorruption:
    def test_manifest_written_and_verified(self, tmp_path):
        hps = hps_tiny()
        ck = ckpt_lib.Checkpointer(str(tmp_path), hps=hps)
        path = ck.save(tiny_state(hps))
        assert ckpt_lib.verify_manifest(path)

    def test_corrupt_latest_falls_back_to_older(
            self, tmp_path, _isolated_obs_and_faults):
        reg = _isolated_obs_and_faults
        hps = hps_tiny()
        ck = ckpt_lib.Checkpointer(str(tmp_path), hps=hps)
        s1 = tiny_state(hps)
        p1 = ck.save(s1)
        s2 = s1._replace(step=s1.step + 5)
        p2 = ck.save(s2)
        assert p1 != p2
        with open(p2, "r+b") as f:  # flip bytes in the newest checkpoint
            f.seek(30)
            f.write(b"\xde\xad\xbe\xef" * 4)
        restored = ck.restore()
        # fell back to the older, intact checkpoint instead of crashing
        assert int(np.asarray(restored.step)) == int(np.asarray(s1.step))
        assert reg.counter("resilience/ckpt_fallbacks_total").value == 1

    def test_explicit_path_surfaces_corruption(self, tmp_path):
        hps = hps_tiny()
        ck = ckpt_lib.Checkpointer(str(tmp_path), hps=hps)
        path = ck.save(tiny_state(hps))
        with open(path, "r+b") as f:
            f.seek(10)
            f.write(b"\x00" * 8)
        # the caller asked for THIS checkpoint: no silent substitution
        with pytest.raises(CheckpointCorruptError, match="checksum"):
            ck.restore(path)

    def test_injected_ckpt_load_fault_falls_back(
            self, tmp_path, _isolated_obs_and_faults):
        reg = _isolated_obs_and_faults
        hps = hps_tiny()
        ck = ckpt_lib.Checkpointer(str(tmp_path), hps=hps)
        s1 = tiny_state(hps)
        ck.save(s1)
        ck.save(s1._replace(step=s1.step + 5))
        # the first load attempt (the newest candidate) fails; the
        # fallback chain serves the older checkpoint
        plan = FaultPlan([FaultSpec("ckpt.load", 1.0, 0, 1)], registry=reg)
        with faultinject.use_plan(plan):
            restored = ck.restore()
        assert restored is not None
        assert int(np.asarray(restored.step)) == int(np.asarray(s1.step))
        assert reg.counter("resilience/ckpt_fallbacks_total").value == 1

    def test_per_job_fault_budget_persists_across_restores(
            self, tmp_path, _isolated_obs_and_faults):
        """HParams(faults="ckpt.load:1.0:0:1") models a dependency that
        fails exactly ONCE then heals: the per-job plan's fire budget
        must survive across restore() calls, not reset per call."""
        hps = hps_tiny(faults="ckpt.load:1.0:0:1")
        ck = ckpt_lib.Checkpointer(str(tmp_path), hps=hps)
        s1 = tiny_state(hps)
        ck.save(s1)
        ck.save(s1._replace(step=s1.step + 5))
        r1 = ck.restore()  # fire 1: newest injected-corrupt -> older
        assert int(np.asarray(r1.step)) == int(np.asarray(s1.step))
        r2 = ck.restore()  # budget spent, fault healed -> newest loads
        assert int(np.asarray(r2.step)) == int(np.asarray(s1.step)) + 5

    def test_load_ckpt_wait_loop_is_observable(
            self, tmp_path, _isolated_obs_and_faults):
        """Satellite 3: a decoder stuck waiting on a trainer is visible
        via ckpt/load_retries_total and ckpt/load_wait_seconds."""
        reg = _isolated_obs_and_faults
        with pytest.raises(FileNotFoundError):
            ckpt_lib.load_ckpt(str(tmp_path), max_retries=2,
                               retry_secs=0.01)
        assert reg.counter("ckpt/load_retries_total").value == 2
        assert reg.gauge("ckpt/load_wait_seconds").value > 0


# -- batcher: etl worker restart budget ------------------------------------

def _vocab():
    return Vocab(words=["the", "cat", "sat", "on", "mat", "."])


class TestEtlWorkerRestarts:
    def test_injected_crashes_restart_within_budget(
            self, _isolated_obs_and_faults):
        reg = _isolated_obs_and_faults
        hps = hps_tiny(batch_size=2, mode="train",
                       faults="etl.worker:1.0:0:2")

        def source():
            return iter([("the cat sat", "<s> the cat . </s>")] * 4)

        b = Batcher("", _vocab(), hps, single_pass=True,
                    example_source=source, max_worker_restarts=3)
        batches = []
        while True:
            batch = b.next_batch()
            if batch is None:
                break
            batches.append(batch)
        # 2 injected crashes consumed 2 restarts; data still flowed
        assert len(batches) == 2
        assert reg.counter(
            "resilience/etl_worker_restarts_total").value == 2

    def test_budget_exhausted_surfaces_worker_crash_error(
            self, _isolated_obs_and_faults):
        hps = hps_tiny(batch_size=2, mode="train",
                       faults="etl.worker:1.0:0")  # crashes forever

        def source():
            return iter([("the cat sat", "<s> the cat . </s>")] * 4)

        b = Batcher("", _vocab(), hps, single_pass=True,
                    example_source=source, max_worker_restarts=2)
        with pytest.raises(WorkerCrashError, match="restart budget spent"):
            for _ in range(100):
                if b.next_batch() is None:
                    break
        assert isinstance(b._fill_error, RuntimeError)

    def test_zero_budget_restores_fail_fast(self, _isolated_obs_and_faults):
        reg = _isolated_obs_and_faults
        hps = hps_tiny(batch_size=2, mode="train",
                       faults="etl.worker:1.0:0:1")

        def source():
            return iter([("the cat sat", "<s> the cat . </s>")] * 4)

        b = Batcher("", _vocab(), hps, single_pass=True,
                    example_source=source, max_worker_restarts=0)
        with pytest.raises(WorkerCrashError):
            for _ in range(100):
                if b.next_batch() is None:
                    break
        assert reg.counter(
            "resilience/etl_worker_restarts_total").value == 0


# -- decoder: deadline degradation -----------------------------------------

DEC_WORDS = ("the a cat dog sat ran mat home big small quick brown fox "
             "jumped over lazy it was day night").split()


class TestDecodeDeadline:
    @pytest.fixture(scope="class")
    def decode_setup(self, tmp_path_factory):
        hps = HParams(batch_size=2, hidden_dim=8, emb_dim=6, vocab_size=24,
                      max_enc_steps=16, max_dec_steps=8, beam_size=2,
                      min_dec_steps=1, max_oov_buckets=4, mode="decode",
                      single_pass=True, decode_deadline_secs=30.0)
        vocab = Vocab(words=DEC_WORDS)
        state = trainer_lib.init_train_state(hps, vocab.size(), seed=0)
        return hps, vocab, state.params

    def _decoder(self, hps, vocab, params, tmp_path, reg):
        def source():
            return iter([
                ("the quick brown fox over the lazy dog .",
                 "<s> the fox . </s>"),
                ("a big cat sat on the small mat .",
                 "<s> the cat sat . </s>")])

        batcher = Batcher("", vocab, hps, single_pass=True,
                          decode_batch_mode="distinct",
                          example_source=source)
        return dec_lib.BeamSearchDecoder(
            hps, vocab, batcher, params=params,
            decode_root=str(tmp_path / "dec"))

    def test_short_deadline_degrades_to_greedy_and_tags(
            self, decode_setup, tmp_path, _isolated_obs_and_faults):
        reg = _isolated_obs_and_faults
        hps, vocab, params = decode_setup
        d = self._decoder(hps, vocab, params, tmp_path, reg)
        batch = d._batcher.next_batch()
        # 1st dispatch: never degraded, and its compile-inclusive wall
        # time is DISCARDED (recording it would lock every later request
        # into greedy); the 2nd full-beam dispatch seeds the estimate
        full = d.decode_batch(batch)
        assert all(not r.degraded for r in full)
        assert d._beam_secs is None
        d.decode_batch(batch)
        assert d._beam_secs is not None
        # 2nd dispatch with a budget far below the estimate -> greedy,
        # tagged degraded, counted
        d._beam_secs = 100.0  # force "budget cannot cover a full beam"
        degraded = d.decode_batch(batch, deadline=Deadline.after(0.5))
        assert all(r.degraded for r in degraded)
        assert len(degraded) == len(full)
        assert reg.counter(
            "resilience/decode_degraded_total").value == len(degraded)
        # a degraded dispatch must not poison the full-beam estimate
        assert d._beam_secs == 100.0

    def test_unbounded_deadline_never_degrades(
            self, decode_setup, tmp_path, _isolated_obs_and_faults):
        reg = _isolated_obs_and_faults
        hps, vocab, params = decode_setup
        d = self._decoder(hps.replace(decode_deadline_secs=0.0), vocab,
                          params, tmp_path, reg)
        batch = d._batcher.next_batch()
        d.decode_batch(batch)
        d._beam_secs = 100.0
        out = d.decode_batch(batch)  # hps deadline 0 = never degrade
        assert all(not r.degraded for r in out)
        assert reg.counter("resilience/decode_degraded_total").value == 0


# -- serve queue under faults (ISSUE 4 chaos satellite) ---------------------

class TestServeChaos:
    """The serve queue under TS_FAULTS=io.read + injected slow batches:
    overload sheds and deadline degradations are COUNTED, and nothing
    hangs — every admitted request resolves within a bound."""

    SERVE_WORDS = ("the a cat dog sat ran mat home big small quick brown "
                   "fox jumped over lazy it was day night").split()

    def test_flaky_source_plus_slow_batches_shed_and_degrade_never_hang(
            self, tmp_path, _isolated_obs_and_faults):
        from textsummarization_on_flink_tpu.serve.errors import (
            ServeOverloadError,
        )
        from textsummarization_on_flink_tpu.serve.server import ServingServer

        reg = _isolated_obs_and_faults
        vocab = Vocab(words=self.SERVE_WORDS)
        hps = HParams(mode="decode", batch_size=2, hidden_dim=8, emb_dim=6,
                      vocab_size=vocab.size(), max_enc_steps=16,
                      max_dec_steps=6, beam_size=2, min_dec_steps=1,
                      max_oov_buckets=4, serve_max_wait_ms=5.0,
                      serve_max_queue=2, decode_deadline_secs=5.0,
                      serve_buckets="16")
        state = trainer_lib.init_train_state(hps, vocab.size(), seed=0)
        inner = dec_lib.BeamSearchDecoder(
            hps, vocab, batcher=None, params=state.params,
            decode_root=str(tmp_path / "serve"))

        class SlowDecoder:
            """Injected slow batches: every dispatch stalls long enough
            for the 2-deep queue to overflow behind it."""

            def decode_batch(self, batch, deadline=None):
                time.sleep(0.1)
                return inner.decode_batch(batch, deadline=deadline)

            def maybe_reload_checkpoint(self, last):
                return last

        # a flapping peer: the first two read attempts die (same indices
        # every run), then the stream replays clean — ResilientSource
        # reconnects with backoff and dedups, exactly like production
        lines = [io_lib.Message(f"u{i}", "the quick brown fox ran", "",
                                "r").to_json() for i in range(12)]
        server_tcp, port = _serve_lines(lines)
        plan = FaultPlan([FaultSpec("io.read", 1.0, 0, 2)], registry=reg)
        serve_server = ServingServer(hps, vocab, decoder=SlowDecoder(),
                                     registry=reg)
        # pre-warm the compile and force the degradation ladder: with a
        # huge full-beam estimate every bounded request degrades to
        # greedy (the decoder's _should_degrade contract)
        inner._beam_warm = True
        inner._beam_secs = 100.0
        admitted, sheds = [], 0
        try:
            with faultinject.use_plan(plan), serve_server:
                src = io_lib.ResilientSource(
                    lambda: io_lib.SocketSource("127.0.0.1", port,
                                                max_count=12),
                    max_reconnects=4, seed=0, sleep=lambda d: None)
                for row in src.rows():
                    try:
                        admitted.append(serve_server.submit(
                            str(row[1]), uuid=str(row[0])))
                    except ServeOverloadError:
                        sheds += 1
                # NEVER hung: every admitted request resolves in bound
                results = [f.result(timeout=120) for f in admitted]
        finally:
            server_tcp.shutdown()
            server_tcp.server_close()
        # the flaky stream reconnected (not silently truncated) ...
        assert reg.counter("resilience/io_reconnects_total").value == 2
        assert plan.stats()["io.read"]["fires"] == 2
        # ... slow batches overflowed the bounded queue into typed sheds
        assert sheds > 0
        assert reg.counter("serve/shed_total").value == sheds
        # ... admitted requests all completed, each degraded to greedy
        # under the enqueue-measured deadline, and all of it is counted
        assert len(results) == len(admitted) == 12 - sheds
        assert all(r.degraded for r in results)
        assert reg.counter("serve/degraded_total").value == len(results)
        assert reg.counter(
            "resilience/decode_degraded_total").value == len(results)
        assert reg.counter("serve/completed_total").value == len(results)

    def test_continuous_mode_chaos_exactly_once_under_faults(
            self, tmp_path, _isolated_obs_and_faults):
        """The ISSUE-6 acceptance chaos run: continuous (slotted) serving
        under io.read faults on the feed, an injected serve.dispatch
        tick failure, slow chunks, and a 2-deep admission queue.  Every
        ADMITTED request must resolve EXACTLY ONCE — with its result or
        the typed injected cause — sheds must be counted, and nothing
        may hang."""
        from textsummarization_on_flink_tpu.serve.errors import (
            ServeOverloadError,
        )
        from textsummarization_on_flink_tpu.serve.server import ServingServer

        reg = _isolated_obs_and_faults
        vocab = Vocab(words=self.SERVE_WORDS)
        hps = HParams(mode="decode", batch_size=2, hidden_dim=8, emb_dim=6,
                      vocab_size=vocab.size(), max_enc_steps=16,
                      max_dec_steps=6, beam_size=2, min_dec_steps=1,
                      max_oov_buckets=4, serve_max_queue=2,
                      serve_mode="continuous", serve_slots=2,
                      serve_refill_chunk=2,
                      faults="serve.dispatch:1.0:11:1")
        state = trainer_lib.init_train_state(hps, vocab.size(), seed=0)
        decoder = dec_lib.BeamSearchDecoder(
            hps, vocab, batcher=None, params=state.params,
            decode_root=str(tmp_path / "cont_chaos"))

        class SlowEngine(NoArena):
            """Real slot engine with injected slow chunks: each step
            stalls long enough for the 2-deep queue to overflow."""

            def __init__(self, inner):
                self._inner = inner
                self.slots = inner.slots
                self.chunk = inner.chunk

            def pack(self, idx, example):
                self._inner.pack(idx, example)

            def step(self):
                time.sleep(0.1)
                return self._inner.step()

            def unpack(self, idx, example):
                return self._inner.unpack(idx, example)

            def release(self, idx):
                self._inner.release(idx)

        engine = SlowEngine(decoder.slot_engine(slots=2, chunk=2))
        lines = [io_lib.Message(f"u{i}", "the quick brown fox ran", "",
                                "r").to_json() for i in range(12)]
        server_tcp, port = _serve_lines(lines)
        plan = FaultPlan([FaultSpec("io.read", 1.0, 0, 2)], registry=reg)
        serve_server = ServingServer(hps, vocab, decoder=decoder,
                                     engine=engine, registry=reg)
        admitted, sheds = [], 0
        try:
            with faultinject.use_plan(plan), serve_server:
                src = io_lib.ResilientSource(
                    lambda: io_lib.SocketSource("127.0.0.1", port,
                                                max_count=12),
                    max_reconnects=4, seed=0, sleep=lambda d: None)
                for row in src.rows():
                    try:
                        admitted.append(serve_server.submit(
                            str(row[1]), uuid=str(row[0])))
                    except ServeOverloadError:
                        sheds += 1
                # NEVER hung, and EXACTLY ONCE: each admitted future
                # resolves with a result or the typed injected cause
                ok, injected = 0, 0
                for f in admitted:
                    try:
                        f.result(timeout=120)
                        ok += 1
                    except RuntimeError as e:
                        assert "injected serve.dispatch fault" in str(e)
                        injected += 1
                # the loop LIVED ON past the injected tick: a fresh
                # post-fault request must serve normally (how many of
                # the streamed rows beat the fault is a thread race —
                # this one cannot)
                post = serve_server.submit("the quick brown fox ran",
                                           uuid="post")
                assert post.result(timeout=120).uuid == "post"
        finally:
            server_tcp.shutdown()
            server_tcp.server_close()
        assert reg.counter("resilience/io_reconnects_total").value == 2
        assert sheds > 0
        assert reg.counter("serve/shed_total").value == sheds
        assert ok + injected == len(admitted) == 12 - sheds
        # the injected tick failure hit at least one resident request
        assert injected >= 1
        assert reg.counter("serve/errors_total").value == injected
        assert reg.counter("serve/completed_total").value == ok + 1

    def test_injected_dispatch_fault_fails_one_batch_not_the_server(
            self, _isolated_obs_and_faults):
        """serve.dispatch injection: the poisoned batch is rejected
        wholesale with the typed cause; the dispatcher survives and the
        next batch serves."""
        from textsummarization_on_flink_tpu.decode.decoder import (
            DecodedResult,
        )
        from textsummarization_on_flink_tpu.serve.server import ServingServer

        reg = _isolated_obs_and_faults
        vocab = Vocab(words=self.SERVE_WORDS)
        hps = HParams(mode="decode", batch_size=2, max_enc_steps=8,
                      max_dec_steps=4, min_dec_steps=1,
                      serve_max_wait_ms=50.0, serve_max_queue=16,
                      faults="serve.dispatch:1.0:3:1")

        class EchoDecoder:
            def decode_batch(self, batch, deadline=None):
                return [DecodedResult(
                            uuid=batch.uuids[b],
                            article=batch.original_articles[b],
                            decoded_words=["ok"], reference="",
                            abstract_sents=[])
                        for b in range(len(batch.uuids))
                        if batch.real_mask[b]]

            def maybe_reload_checkpoint(self, last):
                return last

        server = ServingServer(hps, vocab, decoder=EchoDecoder(),
                               registry=reg)
        with server:
            doomed = server.submit("the cat sat", uuid="doomed")
            with pytest.raises(RuntimeError, match="injected"):
                doomed.result(timeout=30)
            ok = server.submit("the dog ran", uuid="ok")
            assert ok.result(timeout=30).uuid == "ok"
        assert reg.counter("serve/errors_total").value == 1
        assert reg.counter("serve/completed_total").value == 1
        assert reg.counter("resilience/fault/serve.dispatch").value == 1


# -- flight recorder: dumps under injected faults (ISSUE 9) ----------------

class TestFlightRecorderForensics:
    """Acceptance: under injected ``train.step_nan`` and
    ``serve.dispatch`` faults (the existing TS_FAULTS points), a
    ``flight_<reason>.jsonl`` dump exists holding >= the configured ring
    of frames recorded strictly before the trigger fired."""

    # p=0.35 with seed 5 first fires on the 7th fire() call — verified
    # below against the same RNG the fault plan uses, so the ring (4)
    # is guaranteed full of pre-trigger frames
    FAULT_PROB, FAULT_SEED, FIRST_FIRE = 0.35, 5, 7

    def test_seed_fires_on_seventh_call(self):
        import random

        rng = random.Random(self.FAULT_SEED)
        first = next(i for i in range(1, 100)
                     if rng.random() < self.FAULT_PROB)
        assert first == self.FIRST_FIRE

    def test_injected_train_nan_dumps_preceding_steps(self, tmp_path):
        """Six clean steps flush six frames; the injected NaN at step 6
        dumps the newest 4 of them to the train dir."""
        hps = hps_tiny(
            log_root=str(tmp_path), exp_name="t", metrics_every=1,
            flight_frames=4,
            faults=f"train.step_nan:{self.FAULT_PROB}:{self.FAULT_SEED}:1")
        vocab = Vocab(words=["a", "b", "c", "d", "e", "f", "."])
        batch = make_batch(hps, vocab)
        trainer = trainer_lib.Trainer(hps, vocab.size(),
                                      FixedBatcher(batch, 20))
        with pytest.raises(trainer_lib.NonFiniteLossError, match="injected"):
            trainer.train(num_steps=12)
        dump = tmp_path / "t" / "train" / "flight_train_nan.jsonl"
        assert dump.exists()
        lines = [json.loads(ln) for ln in open(dump, encoding="utf-8")]
        header, frames = lines[0], lines[1:]
        assert header["kind"] == "flight" and header["reason"] == "train_nan"
        assert header["context"] == {"step": 6, "injected": True}
        # >= the configured ring, every frame STRICTLY before the trigger
        assert len(frames) == 4 == header["capacity"]
        assert [f["step"] for f in frames] == [2, 3, 4, 5]
        assert all(f["kind"] == "train_step" and "loss" in f
                   and "global_norm" in f and "step_time" in f
                   and "prefetch_depth" in f for f in frames)

    def test_injected_dispatch_fault_dumps_preceding_ticks(
            self, tmp_path, _isolated_obs_and_faults):
        """Continuous mode: six clean chunk ticks frame the ring; the
        injected serve.dispatch failure on the 7th busy tick dumps them
        (each busy tick frames BEFORE its dispatch, so the failing
        tick's own pre-failure frame is included)."""
        from textsummarization_on_flink_tpu.decode.decoder import (
            DecodedResult,
        )
        from textsummarization_on_flink_tpu.serve.server import ServingServer

        reg = _isolated_obs_and_faults
        vocab = Vocab(words=["the", "cat", "sat", "."])
        hps = HParams(
            mode="decode", batch_size=2, max_enc_steps=8, max_dec_steps=4,
            min_dec_steps=1, serve_max_queue=8, serve_mode="continuous",
            serve_slots=2, serve_refill_chunk=2,
            log_root=str(tmp_path), exp_name="s", flight_frames=4,
            faults=f"serve.dispatch:{self.FAULT_PROB}:{self.FAULT_SEED}:1")

        class NeverFinishEngine(NoArena):
            """One resident request, resident forever: every tick is a
            busy tick, so fire() call N == busy tick N."""

            slots = 2

            def __init__(self):
                self.packed = {}

            def pack(self, idx, example):
                self.packed[idx] = example

            def step(self):
                return []

            def unpack(self, idx, example):  # pragma: no cover
                return DecodedResult(uuid=example.uuid, article="",
                                     decoded_words=[], reference="",
                                     abstract_sents=[])

            def release(self, idx):
                self.packed.pop(idx, None)

        class StubDec:
            def maybe_reload_checkpoint(self, last):
                return last

        server = ServingServer(hps, vocab, decoder=StubDec(),
                               engine=NeverFinishEngine(), registry=reg)
        with server:
            fut = server.submit("the cat sat", uuid="u0")
            with pytest.raises(RuntimeError, match="injected serve.dispatch"):
                fut.result(timeout=60)
        dump = tmp_path / "s" / "flight_serve_dispatch.jsonl"
        assert dump.exists()
        lines = [json.loads(ln) for ln in open(dump, encoding="utf-8")]
        header, frames = lines[0], lines[1:]
        assert header["reason"] == "serve_dispatch"
        assert header["context"] == {"error": "RuntimeError"}
        # the full configured ring, recorded strictly before the trigger
        assert len(frames) == 4 == header["capacity"]
        assert all(f["kind"] == "serve_tick" for f in frames)
        ticks = [f["tick"] for f in frames]
        assert ticks == sorted(ticks)
        assert ticks == list(range(ticks[0], ticks[0] + 4))  # consecutive
        assert all(f["occupancy"] == 0.5 and f["refills"] in (0, 1)
                   for f in frames)
        assert server._faults.stats()["serve.dispatch"]["fires"] == 1
