"""The drivers: one for a served cell (traffic kind `open_loop`), one for
a training job (`train_job`).  Each builds the system
under test from the program's normal entry points (`ServingServer`,
`Trainer` over `Batcher` and its `DevicePrefetcher`), warms the cell's
own shapes, measures a window of --seconds, and hands back what the
harness needs: end-to-end numbers, the material for `correct`, and the
sources the per-layer readers read.

Nothing here knows a cell by name: sizes come from the configuration
file, the load from the traffic file.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from harness import traffic as traffic_lib
from harness import weights


class Tracer:
    """Takes one profiler trace of `seconds` starting `start` seconds into
    the window, from a thread of its own, and keeps what the reduction
    needs: the window's host-clock length, the epoch time of the sync
    annotation, and the program's recent host phases at stop time."""

    def __init__(self, log_dir: str, start: float, seconds: float,
                 phases_fn: Optional[Callable[[], list]] = None):
        self.log_dir, self.start, self.seconds = log_dir, start, seconds
        self.phases_fn = phases_fn
        self.window_s = 0.0
        self.sync_epoch_ns: Optional[float] = None
        self.phases: list = []
        self.error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None

    def begin(self, t0: float) -> None:
        self._thread = threading.Thread(target=self._run, args=(t0,),
                                        name="bench-tracer", daemon=True)
        self._thread.start()

    def _run(self, t0: float) -> None:
        import jax

        try:
            time.sleep(max(0.0, t0 + self.start - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench_sync"):
                self.sync_epoch_ns = time.time() * 1e9
            time.sleep(self.seconds)
            self.window_s = time.perf_counter() - a
            if self.phases_fn is not None:
                self.phases = list(self.phases_fn())
            jax.profiler.stop_trace()
        except BaseException as e:  # reported by the harness after join
            self.error = e

    def finish(self, timeout: float = 240.0) -> None:
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():
                raise TimeoutError("the profiler did not stop in time")
        if self.error is not None:
            raise self.error


def program_temp_bytes(jitted, *args, **kw) -> int:
    """XLA's own figure for the scratch ("temp") memory of the compiled
    program that `jitted(*args, **kw)` runs: lowering it again with the
    same shapes finds the same executable in the compile cache."""
    import jax

    def shape(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    args, kw = jax.tree_util.tree_map(shape, (args, kw))
    analysis = jitted.lower(*args, **kw).compile().memory_analysis()
    return int(getattr(analysis, "temp_size_in_bytes", 0) or 0)


def program_hps(cfg: Dict[str, Any], role: str, **extra):
    from textsummarization_on_flink_tpu.config import HParams

    kw = dict(cfg["hparams"])
    kw.update(cfg["deployment"][role])
    kw.update(extra)
    hps = HParams(**kw)
    hps.validate()
    return hps


def make_vocab(cfg: Dict[str, Any], mix: Dict[str, Any]):
    from textsummarization_on_flink_tpu.data.vocab import Vocab

    V = int(cfg["hparams"]["vocab_size"])
    vocab = Vocab(words=traffic_lib.Words(V, mix["article"]).vocabulary())
    if vocab.size() != V:
        raise ValueError(f"vocabulary of {vocab.size()} for vocab_size {V}")
    return vocab


def registry_snapshot(reg) -> Dict[str, Any]:
    """Every series of the program's registry as plain data."""
    out = {}
    for name, labels, kind, payload in reg.series():
        key = name + "".join(f"|{k}={v}" for k, v in labels)
        if kind == "gauge":
            payload = payload[0]
        elif kind == "histogram":
            payload = {k: payload[k] for k in ("count", "sum", "buckets",
                                                "counts", "min", "max")}
        out[key] = payload
    return out


def recent_phases(prof) -> list:
    """The program's phase ledger ring as (start_epoch_ns, end_epoch_ns,
    name)."""
    return [(ts_us * 1e3 - dur * 1e9, ts_us * 1e3, name)
            for ts_us, name, dur, _ in prof.recent_phases()]


# ------------------------------------------------------------------ serve

class ServeRun:
    def __init__(self):
        self.attempted = 0
        self.failed = 0
        self.latencies_ms: List[float] = []  # +inf for failed / unresolved
        self.lateness_ms: List[float] = []
        self.due_s: List[float] = []  # due time of each request, from t0
        self.completed_in_window = 0
        self.window_s = 0.0
        self.finished: List[Any] = []  # (Article, DecodedResult)
        self.tokens_out = 0


def run_serve(cfg, mix, seed: int, seconds: float, work: str, meter,
              tracer_args: Optional[dict], hooks: Dict[str, Any]):
    """Build the server, warm it, drive the mix for `seconds`.  Returns
    (ServeRun, context for the readers).  hooks["setup_done"]() is called
    at the first measured instant."""
    import jax

    from textsummarization_on_flink_tpu.config import resolve_refill_chunk
    from textsummarization_on_flink_tpu.obs import profile as profile_lib
    from textsummarization_on_flink_tpu.pipeline.io import CollectionSink
    from textsummarization_on_flink_tpu.serve.errors import ServeOverloadError
    from textsummarization_on_flink_tpu.serve.server import ServingServer

    hps = program_hps(cfg, "serve", mode="decode", batch_size=1,
                      log_root=work, exp_name="serve")
    V = int(hps.vocab_size)
    vocab = make_vocab(cfg, mix)
    params = weights.make_params(cfg, seed)
    n_req = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    offsets = traffic_lib.arrival_offsets(mix, n_req, seed)
    articles = traffic_lib.make_articles(
        mix, V, n_req, seed, clock=weights.summary_clock(cfg))
    words = traffic_lib.Words(V, mix["article"])
    warm_rng = traffic_lib.rng_for(seed, 9)
    server = ServingServer(hps, vocab, params=params,
                           decode_root=os.path.join(work, "decode"))
    reg = server.registry
    prof = profile_lib.profiler_for(reg)
    run = ServeRun()
    lock = threading.Lock()
    resolved: Dict[str, float] = {}
    results: Dict[str, Any] = {}
    errors: Dict[str, BaseException] = {}
    sink = CollectionSink()

    def on_done(fut, uuid):
        now = time.perf_counter()
        with lock:
            resolved[uuid] = now
            if fut.error is None:
                res = fut.result()
                sink.write(res.as_row())
                res.attn_dists = res.p_gens = None  # not compared; free it
                results[uuid] = res
            else:
                errors[uuid] = fut.error

    server.start()
    try:
        # warm every shape this mix will use: one article at each warm
        # length (each routes to its own prefill bucket), one at a time
        for L in mix["warm_lengths"]:
            w, _, _ = words.draw(warm_rng, int(L))
            server.submit(" ".join(w), uuid=f"warm-{L}",
                          block=True).result(timeout=1100)
        jax.effects_barrier()
        compiles0 = meter.snapshot()[0]
        snap0 = registry_snapshot(reg)
        tracer = None
        if tracer_args is not None:
            tracer = Tracer(tracer_args["dir"], float(mix["trace_start_s"]),
                            float(mix["trace_seconds"]),
                            phases_fn=lambda: recent_phases(prof))
        hooks["setup_done"]()
        t0 = time.perf_counter()
        t_end = t0 + seconds
        if tracer is not None:
            tracer.begin(t0)
        due: Dict[str, float] = {}
        sent: List[Any] = []
        for art, off in zip(articles, offsets):
            t_due = t0 + float(off)
            if t_due >= t_end:
                break
            delay = t_due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            now = time.perf_counter()
            run.lateness_ms.append(1e3 * (now - t_due))
            due[art.uuid] = t_due
            sent.append(art)
            try:
                fut = server.submit(art.text, uuid=art.uuid, block=False)
                fut.add_done_callback(lambda f, u=art.uuid: on_done(f, u))
            except ServeOverloadError as e:
                with lock:
                    errors[art.uuid] = e
                    resolved[art.uuid] = time.perf_counter()
        t_close = time.perf_counter()
        with lock:
            run.completed_in_window = sum(
                1 for u, t in resolved.items()
                if t <= t_end and u in results)
        # every request that was due gets its answer: wait a minute past
        # the close if need be.  Late is late, not wrong.
        drain_until = time.perf_counter() + float(mix.get("drain_seconds", 60))
        while time.perf_counter() < drain_until:
            with lock:
                if len(resolved) >= len(sent):
                    break
            time.sleep(0.01)
        t_drained = time.perf_counter()
        run.window_s = max(t_close, t_end) - t0
        compiles1 = meter.snapshot()[0]
        snap1 = registry_snapshot(reg)
        if tracer is not None:
            tracer.finish()
        # the slot step as the server ran it, through the program's own
        # accessor: XLA's figure for its scratch and, in a traced run,
        # its text (the instruction -> named scope map of the capture)
        compiled = server.compiled_slot_step()
        temp = int(getattr(compiled.memory_analysis(),
                           "temp_size_in_bytes", 0) or 0)
        slot_step_hlo = compiled.as_text() if tracer is not None else None
        del compiled
    finally:
        server.stop(timeout=30.0)
    run.attempted = len(sent)
    for art in sent:
        run.due_s.append(due[art.uuid] - t0)
        if art.uuid in results:
            run.latencies_ms.append(1e3 * (resolved[art.uuid] - due[art.uuid]))
            run.finished.append((art, results[art.uuid]))
            run.tokens_out += len(results[art.uuid].decoded_words)
        else:
            # failed, shed or never answered: slower than every
            # completed request (it is still waiting when the drain ends)
            run.failed += 1
            run.latencies_ms.append(1e3 * (t_drained - due[art.uuid]))
    mean_len = float(np.mean([len(a.ids) for a in sent])) if sent else 0.0
    # the longest stretch in which no summary came back, and when it
    # began: a tick is under half a second, so a stall of the server (or
    # of the whole host: the generator's own lateness shows that) stands
    # out of an untraced run too (PERF.md section 7)
    done = np.sort([resolved[a.uuid] for a in sent if a.uuid in results])
    silence, silence_at = 0.0, 0.0
    if len(done) > 1:
        i = int(np.argmax(np.diff(done)))
        silence, silence_at = float(done[i + 1] - done[i]), float(done[i] - t0)
    ctx = {"registry0": snap0, "registry1": snap1,
           "compiles_in_window": compiles1 - compiles0,
           "tracer": tracer, "window_s": run.window_s,
           "program_temp_bytes": temp, "slot_step_hlo": slot_step_hlo,
           "family": cfg["family"], "hparams": cfg["hparams"],
           "deployment": dict(cfg["deployment"]["serve"],
                              chunk=resolve_refill_chunk(hps),
                              slots=int(hps.serve_slots),
                              param_bytes=weights.param_dtype(cfg).itemsize),
           "harness": {"mean_article_len": mean_len,
                       "summary_tokens_mean":
                           run.tokens_out / max(1, len(run.finished)),
                       "longest_silence_ms": 1e3 * silence,
                       "longest_silence_at_s": silence_at},
           "errors": {u: f"{type(e).__name__}: {e}"
                      for u, e in list(errors.items())[:5]}}
    del server, params
    return run, ctx


# ------------------------------------------------------------------ train

class WindowFeed:
    """The benchmark's own batcher wrapper: hands the real Batcher's
    batches to the trainer's prefetcher while armed with a count or a
    deadline, then reports exhaustion, which ends `Trainer.train()`
    without a change to the trainer.  Keeps the first `keep` batches it
    handed out (every one is consumed: the trainer trains all it is
    given), for the reference to follow."""

    def __init__(self, batcher, keep: int = 3):
        self._batcher = batcher
        self._keep = keep
        self._left: Optional[int] = None
        self._deadline: Optional[float] = None
        self.given = 0
        self.recorded: List[Dict[str, np.ndarray]] = []
        self.transform: Optional[Callable[[Any], Any]] = None  # tests

    def arm(self, count: Optional[int] = None,
            deadline: Optional[float] = None) -> None:
        self._left, self._deadline = count, deadline

    def next_batch(self):
        if self._left is not None and self._left <= 0:
            return None
        if self._deadline is not None \
                and time.perf_counter() >= self._deadline:
            return None
        batch = self._batcher.next_batch()
        if batch is None:
            return None
        if self.transform is not None:
            batch = self.transform(batch)
        if len(self.recorded) < self._keep:
            self.recorded.append({k: np.array(v) for k, v in
                                  batch.as_arrays().items()})
        if self._left is not None:
            self._left -= 1
        self.given += 1
        return batch


def first_grad_norms(p0, p1, lr: float, acc0: float) -> np.ndarray:
    """Per-leaf norm of the first gradient as the optimizer got it,
    worked out from the state after one step.  Adagrad's first update is
    p1 = p0 - lr * g / sqrt(acc0 + g^2); with u = (p0 - p1) / lr that
    gives g = u * sqrt(acc0 / (1 - u^2)).  (The accumulator itself holds
    acc0 + g^2 in float32, where g^2 ~ 1e-8 is below its resolution.)"""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        def leaf(x, y):
            u = (x - y) / lr
            g = u * jnp.sqrt(acc0 / jnp.maximum(1.0 - u * u, 1e-12))
            return jnp.sqrt(jnp.sum(jnp.square(g)))
        return jnp.stack([leaf(x, y) for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))])

    return np.asarray(norms(p0, p1))


def read_losses(train_dir: str) -> List[float]:
    out = {}
    with open(os.path.join(train_dir, "events.jsonl"), encoding="utf-8") as f:
        for line in f:
            rec = json.loads(line)
            if "loss" in rec and "step" in rec:
                out[int(rec["step"])] = float(rec["loss"])
    return [out[k] for k in sorted(out)]


class TrainRun:
    def __init__(self):
        self.steps = 0
        self.window_s = 0.0
        self.tokens = 0
        self.losses: List[float] = []
        self.g1: Optional[np.ndarray] = None
        self.d3: Optional[np.ndarray] = None
        self.batches: List[Dict[str, np.ndarray]] = []


def run_train(cfg, mix, seed: int, seconds: float, work: str, meter,
              tracer_args: Optional[dict], hooks: Dict[str, Any]):
    import jax

    from textsummarization_on_flink_tpu.data import TFExample
    from textsummarization_on_flink_tpu.data.batcher import Batcher
    from textsummarization_on_flink_tpu.data.chunks import write_chunked
    from textsummarization_on_flink_tpu.obs import profile as profile_lib
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib

    from harness import reference as ref

    data_dir = os.path.join(work, "data")
    os.makedirs(data_dir, exist_ok=True)
    hps = program_hps(cfg, "train", mode="train", log_root=work,
                      exp_name="train",
                      data_path=os.path.join(data_dir, "train_*.bin"))
    V = int(hps.vocab_size)
    vocab = make_vocab(cfg, mix)
    rows = traffic_lib.make_training_rows(mix, V, seed)
    write_chunked(os.path.join(data_dir, "train"),
                  [TFExample().set_bytes("article", a.encode())
                   .set_bytes("abstract", b.encode()) for a, b in rows],
                  chunk_size=256)
    del rows
    batcher = Batcher(hps.data_path, vocab, hps, single_pass=False)
    feed = WindowFeed(batcher, keep=3)
    if hooks.get("feed_transform") is not None:
        feed.transform = hooks["feed_transform"]
    params = weights.make_params(cfg, seed)
    state = trainer_lib.init_train_state(hps, V, params=params)
    del params
    # ONE object: the trainer with its compiled step and its state is
    # driven through its first steps here and handed to the window
    trainer = trainer_lib.Trainer(hps, V, feed, state=state)
    reg = trainer._obs
    prof = profile_lib.profiler_for(reg)
    run = TrainRun()
    p0 = weights.make_params(cfg, seed)
    feed.arm(count=1)
    trainer.train(num_steps=0)
    run.g1 = first_grad_norms(p0, trainer.state.params, float(hps.lr),
                              float(hps.adagrad_init_acc))
    feed.arm(count=2)
    trainer.train(num_steps=0)
    run.d3 = ref.leaf_norms(ref.tree_sub(trainer.state.params, p0))
    del p0
    feed.arm(count=int(mix.get("warm_steps", 8)))
    trainer.train(num_steps=0)
    jax.effects_barrier()
    trainer.writer.flush()
    run.losses = read_losses(trainer.train_dir)[:3]
    run.batches = feed.recorded
    compiles0 = meter.snapshot()[0]
    snap0 = registry_snapshot(reg)
    tracer = None
    if tracer_args is not None:
        tracer = Tracer(tracer_args["dir"], float(mix["trace_start_s"]),
                        float(mix["trace_seconds"]),
                        phases_fn=lambda: recent_phases(prof))
    given0 = feed.given
    hooks["setup_done"]()
    t0 = time.perf_counter()
    if tracer is not None:
        tracer.begin(t0)
    feed.arm(deadline=t0 + seconds)
    state = trainer.train(num_steps=0)
    jax.block_until_ready(state.step)
    run.window_s = time.perf_counter() - t0
    run.steps = feed.given - given0
    if int(state.step) != feed.given:
        raise RuntimeError(f"trainer at step {int(state.step)} after "
                           f"{feed.given} batches")
    run.tokens = run.steps * int(hps.batch_size) * (
        int(hps.max_enc_steps) + int(hps.max_dec_steps))
    compiles1 = meter.snapshot()[0]
    snap1 = registry_snapshot(reg)
    if tracer is not None:
        tracer.finish()
    temp = program_temp_bytes(trainer._step_fn, trainer.state,
                              run.batches[0])
    ctx = {"registry0": snap0, "registry1": snap1,
           "compiles_in_window": compiles1 - compiles0,
           "tracer": tracer, "window_s": run.window_s,
           "program_temp_bytes": temp,
           "family": cfg["family"], "hparams": cfg["hparams"],
           "deployment": dict(cfg["deployment"]["train"],
                              param_bytes=weights.param_dtype(cfg).itemsize),
           "harness": {"steps": run.steps}}
    trainer.writer.close()
    del trainer, state
    return run, ctx
