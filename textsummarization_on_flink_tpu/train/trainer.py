"""Training/eval loops: jitted train step, NaN watchdog, metrics, timing.

Rebuilds the reference's training stack TPU-first:
  * `run_training` / `FlinkTrainer.train` (run_summarization.py:212-244,
    train.py:89-125) -> `Trainer.train`: per-step loss + wall-clock logging,
    summaries, non-finite-loss watchdog (train.py:107-108), optional
    step limit (StopAtStepHook parity, train.py:70-72).
  * `run_eval` (run_summarization.py:247-292) -> `Evaluator.run`:
    exponentially-smoothed running-average loss (decay .99, clipped at 12,
    run_summarization.py:105-129) driving best-model selection.
  * The TF1 PS/worker + MonitoredTrainingSession machinery is replaced by
    a single jitted step (sharded over the mesh in parallel/ for DP).

Summaries are JSON-lines under `<log_root>/<exp_name>/<job>/events.jsonl`
(the reference's TensorBoard scalars, minus the TF dependency).
"""

from __future__ import annotations

import functools
import json
import logging
import os
import time
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.obs import flightrec
from textsummarization_on_flink_tpu.obs import http as obs_http
from textsummarization_on_flink_tpu.obs import profile as profile_lib
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.models import get_family
from textsummarization_on_flink_tpu.resilience import faultinject
from textsummarization_on_flink_tpu.train import optim

log = logging.getLogger(__name__)

Array = jax.Array
PyTree = Any


class TrainState(NamedTuple):
    params: PyTree
    opt_state: optim.AdagradState
    step: Array  # scalar int32 global step


class StepMetrics(NamedTuple):
    loss: Array
    coverage_loss: Array
    total_loss: Array
    global_norm: Array


def opt_state_dtype(hps: HParams):
    """Adagrad accumulator storage dtype for this config (None = follow
    the param dtype; jnp.bfloat16 under --opt_state_dtype=bfloat16)."""
    if getattr(hps, "opt_state_dtype", "float32") == "bfloat16":
        return jnp.bfloat16
    return None


def init_train_state(hps: HParams, vsize: int, seed: Optional[int] = None,
                     params: Optional[PyTree] = None) -> TrainState:
    if params is None:
        params = get_family(hps.model_family).init_params(
            hps, vsize, jax.random.PRNGKey(seed if seed is not None else hps.seed))
    return TrainState(params=params,
                      opt_state=optim.adagrad_init(params,
                                                   hps.adagrad_init_acc,
                                                   dtype=opt_state_dtype(hps)),
                      step=jnp.zeros((), jnp.int32))


def cast_opt_state(hps: HParams, state: TrainState) -> TrainState:
    """Align a state's accumulator dtype with --opt_state_dtype (e.g. a
    checkpoint restored as f32 — npz cannot hold bf16, so the
    checkpointer widens on save — resuming a bf16-state run)."""
    dtype = opt_state_dtype(hps) or jnp.float32
    acc = state.opt_state.accumulators
    leaves = jax.tree_util.tree_leaves(acc)
    if all(getattr(x, "dtype", None) == dtype for x in leaves):
        return state
    return state._replace(opt_state=optim.AdagradState(
        accumulators=jax.tree_util.tree_map(
            lambda a: jnp.asarray(a).astype(dtype), acc)))


def make_loss_fn(hps: HParams):
    """(params, arrays) -> (objective, TrainOutput) — the ONE definition
    of the training objective, shared by make_train_step and the
    explicit-collective sharded step (parallel/mesh.py) so the two can
    never drift."""
    family = get_family(hps.model_family)

    def loss_fn(params: PyTree, arrays: Dict[str, Array]):
        out = family.forward_train(params, hps, arrays)
        # minimize total_loss when coverage is on (model.py:291)
        objective = out.total_loss if hps.coverage else out.loss
        return objective, out

    return loss_fn


def make_grad_fn(hps: HParams) -> Callable:
    """(params, arrays) -> (grads, (loss, coverage_loss, total_loss)) —
    the default gradient computation: one jax.grad of the shared loss
    objective, reductions left to XLA (under pjit the partitioner
    inserts the dp gradient psum in the grads' own dtype).  The sharded
    step builder (parallel/mesh.py) substitutes a registry-driven
    variant when the grad wire dtype is annotated."""
    loss_fn_ = make_loss_fn(hps)

    def grad_fn(params: PyTree, arrays: Dict[str, Array]):
        grads, out = jax.grad(
            lambda p: loss_fn_(p, arrays), has_aux=True)(params)
        return grads, (out.loss, out.coverage_loss, out.total_loss)

    return grad_fn


def make_train_step(hps: HParams, grad_fn: Optional[Callable] = None,
                    ) -> Callable[[TrainState, Dict[str, Array]],
                                  Tuple[TrainState, StepMetrics]]:
    """Build the pure train-step function (jit it, or pjit via parallel/).

    The step BODY (clip -> Adagrad -> state/metrics) exists only here:
    every path — single-device jit, the pjit mesh step, and the
    bf16-wire collective variant — shares it and differs solely in the
    `grad_fn` that produces (grads, scalar losses) (ISSUE 8: one jitted
    step, layout and wire dtype decided by the sharding registry)."""

    grad_fn_ = grad_fn if grad_fn is not None else make_grad_fn(hps)

    def train_step(state: TrainState, arrays: Dict[str, Array]):
        grads, (loss, cov_loss, total) = grad_fn_(state.params, arrays)
        grads, gnorm = optim.clip_by_global_norm(grads, hps.max_grad_norm)
        new_params, new_opt = optim.adagrad_update(
            grads, state.opt_state, state.params, hps.lr)
        new_state = TrainState(params=new_params, opt_state=new_opt,
                               step=state.step + 1)
        metrics = StepMetrics(loss=loss, coverage_loss=cov_loss,
                              total_loss=total, global_norm=gnorm)
        return new_state, metrics

    return train_step


def make_eval_step(hps: HParams):
    family = get_family(hps.model_family)

    def eval_step(params: PyTree, arrays: Dict[str, Array]) -> StepMetrics:
        out = family.forward_train(params, hps, arrays)
        return StepMetrics(loss=out.loss, coverage_loss=out.coverage_loss,
                           total_loss=out.total_loss,
                           global_norm=jnp.zeros(()))
    return eval_step


def calc_running_avg_loss(loss: float, running_avg_loss: float,
                          decay: float = 0.99) -> float:
    """Early-stopping smoother (run_summarization.py:105-129)."""
    if running_avg_loss == 0:
        running_avg_loss = loss
    else:
        running_avg_loss = running_avg_loss * decay + (1 - decay) * loss
    return min(running_avg_loss, 12)


class SummaryWriter:
    """JSONL scalar summaries (TensorBoard-writer stand-in).  Default
    cadence flushes every record; flush_every=k buffers k records per
    flush (the reference flushes every 100 steps,
    run_summarization.py:242-244).  Multi-host: only the chief writes
    (is_chief MonitoredTrainingSession role, train.py:74-81); other hosts
    get a no-op writer so a shared log_root sees one record per step.

    Robustness (ISSUE 1 satellite 2): a deleted/rotated log directory
    must never crash the train loop — the writer recreates the directory
    and reopens the file; a persistent failure drops the record and
    counts it in the ``train/summary_write_errors`` obs counter."""

    def __init__(self, directory: str, flush_every: int = 1,
                 registry: Optional[obs.Registry] = None):
        from textsummarization_on_flink_tpu.parallel import distributed

        self._dir = directory
        self._flush_every = max(int(flush_every), 1)
        self._unflushed = 0
        self._chief = distributed.is_chief()
        self._f = None
        reg = registry if registry is not None else obs.registry()
        self._write_errors = reg.counter("train/summary_write_errors")
        if self._chief:
            self._path = os.path.join(directory, "events.jsonl")
            self._open()

    def _open(self) -> bool:
        try:
            os.makedirs(self._dir, exist_ok=True)
            self._f = open(self._path, "a", encoding="utf-8")
            return True
        except OSError:
            self._f = None
            return False

    def scalars(self, step: int, **values: float) -> None:
        if not self._chief:
            return
        rec = {"step": int(step)}
        rec.update({k: float(v) for k, v in values.items()})
        line = json.dumps(rec) + "\n"
        # POSIX keeps writes to an unlinked file succeeding silently, so
        # a rotated log dir must be detected by path, not by exception.
        # Stat at batch start and just before a flush — not on every
        # buffered write — and count buffered records the rotation ate.
        if (self._f is not None
                and (self._unflushed == 0
                     or self._unflushed + 1 >= self._flush_every)
                and not os.path.exists(self._path)):
            self._drop_buffered()
        for _attempt in (0, 1):
            if self._f is None and not self._open():
                continue
            try:
                self._f.write(line)
                self._unflushed += 1
                if self._unflushed >= self._flush_every:
                    self._f.flush()
                    self._unflushed = 0
                return
            except (OSError, ValueError):  # rotated dir / closed file
                self._drop_buffered()
        self._write_errors.inc()
        log.warning("summary write failed (rotated log dir?); record for "
                    "step %d dropped", step)

    def _drop_buffered(self) -> None:
        """Close a dead file handle; any buffered-but-unflushed records
        went into the unlinked inode, so count them as write errors
        rather than losing them silently."""
        if self._unflushed:
            self._write_errors.inc(self._unflushed)
            log.warning("summary log dir rotated; %d buffered records "
                        "lost", self._unflushed)
        try:
            self._f.close()
        except (OSError, ValueError):  # double-close / rotated-dir close
            pass
        self._f = None
        self._unflushed = 0

    def flush(self) -> None:
        if self._f is not None:
            try:
                self._f.flush()
                self._unflushed = 0
            except (OSError, ValueError):
                self._write_errors.inc()

    def close(self) -> None:
        if self._f is not None:
            try:
                self._f.close()
            except (OSError, ValueError):
                pass
            self._f = None


class NonFiniteLossError(RuntimeError):
    """Raised by the NaN/Inf watchdog (train.py:107-108 parity)."""


class NanLossError(NonFiniteLossError):
    """Divergence recovery exhausted its budgets (RESILIENCE.md): the
    watchdog skipped ``hps.nan_skip_steps`` batches and rolled back
    ``hps.nan_max_rollbacks`` times, and the loss still went non-finite.
    A ``NonFiniteLossError`` subclass so pre-existing watchdog handlers
    keep working."""


class _DivergenceRecovery:
    """Armed NaN/Inf recovery state (hps.nan_skip_steps > 0 or
    hps.nan_max_rollbacks > 0).

    Recovery ladder on a non-finite dispatch:
      1. SKIP — discard the dispatch (params revert to the pre-step
         state; the trainer runs without buffer donation when armed, so
         the reference is still live) and try the next batch, up to
         ``nan_skip_steps`` consecutive skips; any finite dispatch
         resets the budget.
      2. ROLLBACK — restore the last good checkpoint (or, without a
         checkpointer / before the first save, the host-side last-good
         snapshot) and cut the LR by ``nan_lr_cut``; up to
         ``nan_max_rollbacks`` times.
      3. RAISE — ``NanLossError``.

    Counters: ``resilience/train/nan_skips_total``,
    ``resilience/train/rollbacks_total``; gauge
    ``resilience/train/lr_scale``.
    """

    def __init__(self, hps: HParams, checkpointer: Any,
                 registry: obs.Registry, initial_state: "TrainState"):
        self.hps = hps
        self.checkpointer = checkpointer
        self.skips_left = hps.nan_skip_steps
        self.rollbacks_left = hps.nan_max_rollbacks
        self.lr_scale = 1.0
        self._c_skips = registry.counter("resilience/train/nan_skips_total")
        self._c_rollbacks = registry.counter(
            "resilience/train/rollbacks_total")
        self._g_lr_scale = registry.gauge("resilience/train/lr_scale")
        self._g_lr_scale.set(1.0)
        # rollback fallback when no checkpoint exists yet (the initial
        # state is always good); refreshed only when there is no
        # checkpointer to restore from, and then only every
        # SNAPSHOT_EVERY good dispatches — a per-step device_get of the
        # full state (params + optimizer moments) would serialize every
        # dispatch, and rollback semantics only promise "a known-good
        # earlier state", not the newest one
        self.snapshot = jax.device_get(initial_state)
        self._good_since_snapshot = 0

    SNAPSHOT_EVERY = 10

    def note_good(self, state: "TrainState") -> None:
        self.skips_left = self.hps.nan_skip_steps  # consecutive budget
        if self.checkpointer is None:
            self._good_since_snapshot += 1
            if self._good_since_snapshot >= self.SNAPSHOT_EVERY:
                self.snapshot = jax.device_get(state)
                self._good_since_snapshot = 0

    def next_action(self) -> str:
        if self.skips_left > 0:
            return "skip"
        if self.rollbacks_left > 0:
            return "rollback"
        return "raise"

    def take_skip(self) -> None:
        self.skips_left -= 1
        self._c_skips.inc()

    def take_rollback(self) -> "TrainState":
        """Consume one rollback: cut the LR and return the state to
        resume from (host-side leaves; the next dispatch re-transfers)."""
        self.rollbacks_left -= 1
        self.skips_left = self.hps.nan_skip_steps
        self.lr_scale *= self.hps.nan_lr_cut
        self._g_lr_scale.set(self.lr_scale)
        self._c_rollbacks.inc()
        restored = (self.checkpointer.restore()
                    if self.checkpointer is not None else None)
        return restored if restored is not None else self.snapshot


class PrefetchError(RuntimeError):
    """The DevicePrefetcher's worker thread failed; the original cause
    is chained (``raise ... from``).  Typed so consumers can tell an
    input-pipeline death from any other RuntimeError (ISSUE 1 satellite
    1) — and a RuntimeError subclass so pre-existing handlers keep
    working."""


class DevicePrefetcher:
    """Double-buffered host->device feed (SURVEY §2.5 'intra-op
    threading' row: the reference keeps the feed queue full with 16+4
    batcher threads; on TPU the remaining stall is the synchronous H2D
    copy, hidden here by transferring batch N+1 while N computes).

    Wraps any batcher; `next_batch()` returns (batch, device_arrays).

    Failure contract: a worker-thread error surfaces on the NEXT
    `next_batch()` call as a typed PrefetchError — the consumer polls
    rather than parking forever in a blocking get, so a pump death can
    never strand the train loop on a drained queue.

    Telemetry (obs/): `train/prefetch_queue_depth` gauge (sampled per
    consumer pull), `train/prefetch_starvation_total` (pulls after the
    first delivered batch that found the queue empty — the device
    out-ran the input pipeline; cold-start warmup before batch one is
    expected latency, not starvation, and is not counted),
    `train/prefetch_errors_total`, `train/prefetch_batches_total`.
    """

    def __init__(self, batcher: Any, transfer: Callable[[Dict], Dict],
                 depth: int = 2,
                 registry: Optional[obs.Registry] = None):
        import queue as queue_lib
        import threading

        self._batcher = batcher
        self._transfer = transfer
        self._q: Any = queue_lib.Queue(maxsize=max(depth, 1))
        self._done = object()
        self._stopped = threading.Event()
        self._delivered_any = False
        self.error: Optional[BaseException] = None
        reg = registry if registry is not None else obs.registry()
        self._g_depth = reg.gauge("train/prefetch_queue_depth")
        self._c_starved = reg.counter("train/prefetch_starvation_total")
        self._c_errors = reg.counter("train/prefetch_errors_total")
        self._c_batches = reg.counter("train/prefetch_batches_total")
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        import queue as queue_lib

        while not self._stopped.is_set():
            try:
                self._q.put(item, timeout=0.2)
                return True
            except queue_lib.Full:
                continue
        return False

    def _pump(self) -> None:
        try:
            while not self._stopped.is_set():
                batch = self._batcher.next_batch()
                if batch is None:
                    break
                # the device_put happens HERE, ahead of the consumer
                if not self._put((batch, self._transfer(batch.as_arrays()))):
                    return  # stopped while parked on a full queue
        except BaseException as e:  # re-raised by the consumer
            self.error = e
            self._c_errors.inc()
            log.exception("device prefetcher failed")
        finally:
            self._put(self._done)

    def next_batch(self):
        import queue as queue_lib

        self._g_depth.set(self._q.qsize())
        starved = False
        while True:
            try:
                item = self._q.get(timeout=0.2)
                break
            except queue_lib.Empty:
                # the consumer is ahead of the pump: either genuine
                # input starvation (counted once per pull, and only
                # after the first batch — cold-start warmup is not the
                # device out-running the pipeline) or the pump died
                # before parking its _done sentinel — surface the typed
                # error instead of waiting forever
                if not starved and self._delivered_any:
                    starved = True
                    self._c_starved.inc()
                if self.error is not None and self._q.empty():
                    raise PrefetchError(
                        "input pipeline failed mid-training") from self.error
        if item is self._done:
            if self.error is not None:
                raise PrefetchError(
                    "input pipeline failed mid-training") from self.error
            return None
        self._c_batches.inc()
        self._delivered_any = True
        return item

    def stop(self) -> None:
        """Reap the pump thread (a limit/abort exit must not keep draining
        the shared source)."""
        self._stopped.set()
        self._thread.join(timeout=10.0)


class Trainer:
    """Single-host training driver.

    batcher: anything with next_batch() -> Batch|None (data/batcher.py or a
    streaming bridge).  checkpointer: optional, saves every
    `checkpoint_secs` (Supervisor save_model_secs=60 parity,
    run_summarization.py:198) and at the end.
    """

    def __init__(self, hps: HParams, vsize: int, batcher: Any,
                 state: Optional[TrainState] = None,
                 checkpointer: Optional[Any] = None,
                 checkpoint_secs: float = 60.0,
                 checkpoint_steps: int = 0,
                 metrics_every: int = 0,
                 train_dir: Optional[str] = None,
                 step_fn: Optional[Callable] = None):
        self.hps = hps
        self.batcher = batcher
        # Metrics cadence: fetching metrics is a blocking D2H sync that
        # serializes dispatch (and defeats DevicePrefetcher), so losses
        # are fetched/logged/NaN-checked in windows of `metrics_every`
        # steps.  0 = auto: per-step under --debug (exact watchdog, the
        # reference's per-step logging), every 10 steps otherwise.  The
        # summary JSONL still gets one record per step either way.
        self.metrics_every = (metrics_every
                              or getattr(hps, "metrics_every", 0)
                              or (1 if hps.debug else 10))
        # Checkpoint cadence: `checkpoint_steps` (kwarg or the
        # --checkpoint_steps flag) triggers on STEP boundaries — REQUIRED
        # on multi-host, where save() is collective and a wall-clock
        # trigger would fire at different steps per host (hard guard in
        # _train_loop).  Without it, single-host keeps the reference's
        # save_model_secs wall-clock behavior (run_summarization.py:198).
        # With steps_per_dispatch=k, the wall-clock check (and the
        # profiler start/stop) runs only at dispatch boundaries, so both
        # quantize to k steps — same cadence note as metrics_every above.
        self.checkpoint_steps = (checkpoint_steps
                                 or getattr(hps, "checkpoint_steps", 0))
        self.state = state if state is not None else init_train_state(hps, vsize)
        # a restored checkpoint always holds f32 accumulators (npz cannot
        # represent bf16); re-narrow when this run stores them in bf16
        self.state = cast_opt_state(hps, self.state)
        # k train steps per device dispatch (an on-device scan over k
        # stacked batches — config.py steps_per_dispatch).  --debug pins
        # k=1: the exact per-step watchdog needs per-dispatch fetches.
        self.steps_per_dispatch = max(
            1 if hps.debug else getattr(hps, "steps_per_dispatch", 1), 1)
        self._multi_step_cache: Dict[int, Callable] = {}
        self.checkpointer = checkpointer
        self.checkpoint_secs = checkpoint_secs
        self.train_dir = train_dir or os.path.join(
            hps.log_root or ".", hps.exp_name or "exp", "train")
        # observability (OBSERVABILITY.md `train/` namespace); hps.obs
        # False runs this job dark via the null registry
        self._obs = obs.registry_for(hps)
        self._m_step_time = self._obs.histogram("train/step_time_seconds")
        self._m_host_wait = self._obs.histogram("train/host_wait_seconds")
        self._m_fetch = self._obs.histogram("train/metrics_fetch_seconds")
        self._c_steps = self._obs.counter("train/steps_total")
        self._c_examples = self._obs.counter("train/examples_total")
        self._c_nan = self._obs.counter("train/nan_watchdog_total")
        self._c_dump_errors = self._obs.counter("train/nan_dump_errors_total")
        # same gauge instance the DevicePrefetcher writes (get-or-create
        # by name): read per flushed step into flight-recorder frames
        self._g_prefetch = self._obs.gauge("train/prefetch_queue_depth")
        # run-scoped trace root (ISSUE 9): metrics-flush spans carry the
        # run's trace_id so one training run's spans link in events.jsonl
        # the way one serve request's do
        self._trace = (obs.TraceContext.new() if self._obs.enabled
                       else None)
        # the phase ledger (obs/profile.py, ISSUE 16): the loop's
        # host-wait/step-dispatch/metrics-flush/checkpoint sub-phases
        # bracketed by a per-round wall; dark jobs get the null
        # profiler (constant-return, no per-step allocation)
        self._prof = profile_lib.profiler_for(self._obs)
        if getattr(hps, "profile_analytic", False):
            # analytic train-step pricing for the divergence sentinel —
            # AOT cost analysis runs off the hot path (provider thread)
            cost_hps = hps
            self._prof.register_cost(
                "train/step_dispatch", "step",
                lambda: __import__("__graft_entry__").train_step_cost(
                    cost_hps))
        # failure flight recorder: per-step frames ring in memory and
        # dump to <train_dir>/flight_<reason>.jsonl when the NaN
        # watchdog / divergence recovery fires (OBSERVABILITY.md)
        if self._obs.enabled and getattr(hps, "flight_frames", 0) > 0:
            flightrec.install_flight_recorder(
                self._obs, self.train_dir, capacity=hps.flight_frames)
        # live exposition plane (off unless TS_OBS_HTTP /
        # HParams(obs_http_port) enables it; one server per process)
        obs_http.maybe_serve(self._obs, hps)
        # resilience (RESILIENCE.md): the fault plan is resolved ONCE so
        # the per-point RNG streams stay deterministic across the run;
        # unarmed jobs hold the null singleton (fire() is `return False`)
        self._faults = faultinject.plan_for(hps)
        armed = hps.nan_skip_steps > 0 or hps.nan_max_rollbacks > 0
        self._recovery: Optional[_DivergenceRecovery] = None
        if armed:
            if hps.dp * hps.tp * hps.sp > 1 or jax.process_count() > 1:
                raise ValueError(
                    "divergence recovery (nan_skip_steps/nan_max_rollbacks) "
                    "is single-host, default-mesh only: a skip must revert "
                    "to the pre-step state, which the sharded/multi-host "
                    "collective step donates away")
            if step_fn is not None:
                raise ValueError(
                    "divergence recovery requires the trainer-built train "
                    "step (LR cuts rebuild it); drop the custom step_fn or "
                    "disarm nan_skip_steps/nan_max_rollbacks")
            self._recovery = _DivergenceRecovery(
                hps, checkpointer, self._obs, self.state)
        self.writer = SummaryWriter(
            self.train_dir,
            flush_every=getattr(hps, "summary_flush_every", 1),
            registry=self._obs)
        # TS_OBS_EVENTS=1: stream finished spans into the SAME
        # events.jsonl the scalar summaries use (the unified format,
        # OBSERVABILITY.md) through the bounded background flusher.
        # Opt-in: every sink is a daemon thread, and most Trainer
        # constructions (tests, short fits) don't want one.
        if (self._obs.enabled and self._obs.event_sink is None
                and os.environ.get("TS_OBS_EVENTS", "").lower()
                in ("1", "on", "true", "yes")):
            from textsummarization_on_flink_tpu.obs import export as obs_export

            obs_export.install_event_sink(self._obs, self.train_dir)
        self._shard_batch: Optional[Callable] = None
        if step_fn is None:
            if hps.dp * hps.tp * hps.sp > 1:
                # SPMD over the (dp, tp, sp) mesh: the sharded step IS the
                # distributed backend (parallel/mesh.py) — XLA inserts the
                # dp-axis gradient psum and tp/sp collectives.
                from textsummarization_on_flink_tpu.parallel import mesh as mesh_lib

                mesh_lib.validate_divisibility(hps, self.state.params)
                plan = mesh_lib.make_mesh(hps)
                self.state = mesh_lib.shard_train_state(plan, self.state)
                if jax.process_count() > 1:
                    # Each host's batcher must feed ITS shard of the
                    # global batch: batch_size/process_count rows per
                    # host (configure the batcher with the LOCAL size;
                    # hps.batch_size stays the global batch).
                    self._shard_batch = mesh_lib.make_host_local_transfer(
                        plan, hps.batch_size, label="train")
                else:
                    self._shard_batch = functools.partial(
                        mesh_lib.shard_batch, plan)
                step_fn = mesh_lib.make_sharded_train_step(
                    plan, state=self.state)
            else:
                step_fn = self._build_step_fn()
        self._step_fn = step_fn

    def _build_step_fn(self) -> Callable:
        """The single-device jitted step.  Unarmed: donates the input
        state (lowest memory).  Armed divergence recovery: NO donation —
        a skip reverts to the pre-step state, so its buffers must
        survive the dispatch — and the LR carries the rollback cut."""
        hps = self.hps
        if self._recovery is not None:
            if self._recovery.lr_scale != 1.0:
                hps = hps.replace(lr=hps.lr * self._recovery.lr_scale)
            return jax.jit(make_train_step(hps))
        return jax.jit(make_train_step(hps), donate_argnums=0)

    def train(self, num_steps: Optional[int] = None) -> TrainState:
        """Run until num_steps (hps.num_steps when None; 0 = until the
        batcher is exhausted).

        Profiling (SURVEY §5.1): the reference logs per-step wall clock
        only; here HParams(profile_dir=...) — or the legacy
        TS_PROFILE_DIR env fallback — captures a JAX/XLA profiler trace
        of steps 2-7 (post-compilation) for TensorBoard's trace viewer,
        and the capture window lands in the profiler ledger as a
        `profiler_capture` note (ISSUE 16) so /profile shows WHEN a
        trace was taken alongside the phase table it annotates.
        """
        limit = self.hps.num_steps if num_steps is None else num_steps
        # checkpoint cadence is a DURATION: monotonic, never wall clock
        # (TS003 — an NTP slew/suspend must not skip or double a save)
        last_ckpt = time.monotonic()
        # HParam wins over the env fallback: a config-driven run must
        # not be silently redirected by ambient shell state
        profile_dir = (getattr(self.hps, "profile_dir", "")
                       or os.environ.get("TS_PROFILE_DIR"))
        # anchor to the first step of THIS run (may resume past step 2)
        profile_start = int(self.state.step) + 2
        profile_stop = profile_start + 5
        try:
            return self._train_loop(limit, last_ckpt, profile_dir,
                                    profile_start, profile_stop)
        finally:
            # a finished (or aborted) run is not a WEDGED run: retire
            # the loop heartbeat so /healthz doesn't 503 a process that
            # trained to completion and moved on (e.g. train -> serve)
            obs_http.retire_heartbeat(self._obs, "train/loop")
            if profile_dir:
                try:  # finalize a trace left open by an exception/NaN abort
                    jax.profiler.stop_trace()
                except RuntimeError:
                    pass  # no trace active

    def _train_loop(self, limit, last_ckpt, profile_dir, profile_start,
                    profile_stop) -> TrainState:
        multihost = jax.process_count() > 1
        if multihost and not limit:
            # Collective ops (train step, checkpoint gather) must stay in
            # lockstep; per-host data shards exhaust at different steps,
            # so an until-exhausted run cannot be multi-host-safe.
            raise ValueError(
                "multi-host training requires an explicit num_steps limit "
                "(per-host streams may end at different steps, desyncing "
                "collectives)")
        if multihost and getattr(self.hps, "single_pass", False):
            # Even with a limit, a finite per-host stream can end early on
            # one host while the others still issue collective steps —
            # that host would then enter the collective checkpoint save
            # and hang the job.
            raise ValueError(
                "multi-host training cannot use single_pass (finite "
                "per-host streams end at different steps, desyncing "
                "collectives); stream an infinite shuffled pass instead")
        if multihost and self.checkpointer is not None \
                and self.checkpoint_steps <= 0:
            # A wall-clock cadence would fire at different steps on
            # different hosts and desync the collective save; no silent
            # reinterpretation of checkpoint_secs as steps (VERDICT r3).
            raise ValueError(
                "multi-host training with a checkpointer requires an "
                "explicit checkpoint_steps cadence (--checkpoint_steps "
                "or Trainer(checkpoint_steps=...)); the wall-clock "
                "checkpoint_secs cadence is single-host only")
        transfer = self._shard_batch if self._shard_batch is not None \
            else jax.device_put
        # depth covers one full multi-step pull plus a batch in flight,
        # so a k-batch dispatch never starves on the depth-2 default
        prefetcher = DevicePrefetcher(
            self.batcher, transfer,
            depth=max(2, self.steps_per_dispatch + 1),
            registry=self._obs)
        try:
            return self._train_steps(limit, last_ckpt, profile_dir,
                                     profile_start, profile_stop,
                                     prefetcher, multihost)
        finally:
            prefetcher.stop()

    def _multi_step(self, k: int) -> Callable:
        """k train steps as ONE dispatch: an on-device lax.scan over k
        batches stacked on a leading axis (steps_per_dispatch — the TPU
        steps_per_execution pattern; k-fold fewer host round trips).
        Numerically identical to k sequential dispatches."""
        fn = self._multi_step_cache.get(k)
        if fn is None:
            step_fn = self._step_fn

            def multi(state, stacked):
                return jax.lax.scan(
                    lambda s, arrays: step_fn(s, arrays), state, stacked)

            # armed recovery: the pre-dispatch state must survive a skip
            fn = (jax.jit(multi) if self._recovery is not None
                  else jax.jit(multi, donate_argnums=0))
            self._multi_step_cache[k] = fn
        return fn

    def _flush_metrics(self, pending, window_dt) -> None:
        """Fetch a window of device-resident metrics in one D2H transfer,
        log + summarize each step, and run the NaN watchdog
        (train.py:107-108 parity, detection deferred <= metrics_every
        steps unless --debug pins the window to 1).

        pending: [(first_step, n_steps, metrics, arrays|None)] — metrics
        leaves are scalars when n_steps == 1, [n_steps]-vectors from the
        multi-step scan otherwise."""
        if not pending:
            return
        with self._prof.phase("train/metrics_flush", parent=self._trace,
                              step=pending[0][0]):
            # the fetch is a blocking D2H sync — its cost is exactly the
            # dispatch-serialization price the windowing amortizes, so it is
            # measured (train/metrics_fetch_seconds) rather than guessed
            t_fetch = time.perf_counter()
            fetched = jax.device_get([m for _, _, m, _ in pending])
            self._m_fetch.observe(time.perf_counter() - t_fetch)
            total = sum(n for _, n, _, _ in pending)
            step_time = window_dt / max(total, 1)
            for _ in range(total):  # window average, one sample per step
                self._m_step_time.observe(step_time)
            log.info("seconds for training step: %.3f (avg over %d)",
                     step_time, total)
            prefetch_depth = self._g_prefetch.value
            for (step0, n, _, arrays), m in zip(pending, fetched):
                for i in range(n):
                    step = step0 + i
                    pick = (lambda x: x) if n == 1 else (lambda x: x[i])
                    loss = float(pick(m.loss))
                    log.info("loss: %f", loss)
                    scalars = dict(loss=loss,
                                   total_loss=float(pick(m.total_loss)),
                                   global_norm=float(pick(m.global_norm)),
                                   step_time=step_time)
                    if self.hps.coverage:
                        cl = float(pick(m.coverage_loss))
                        log.info("coverage_loss: %f", cl)
                        scalars["coverage_loss"] = cl
                    # per-step flight frame: what the NaN post-mortem reads
                    # (a finite-or-not loss ships either way — the LAST
                    # frames before a blowup are the interesting ones)
                    flightrec.record(
                        self._obs, "train_step", step=step, loss=loss,
                        global_norm=float(pick(m.global_norm)),
                        step_time=round(step_time, 6),
                        prefetch_depth=prefetch_depth)
                    if not np.isfinite(loss):
                        self._c_nan.inc()
                        self._dump_nan_batch(step, arrays)
                        flightrec.trigger(self._obs, "train_nan", step=step)
                        # worst case: the bad step opens a window that only
                        # flushes at >= metrics_every steps, reached in whole
                        # k-step dispatches — so up to metrics_every + k - 2
                        # steps can run past it (ADVICE r3)
                        lag = max(max(self.metrics_every, 1)
                                  + self.steps_per_dispatch - 2, 0)
                        raise NonFiniteLossError(
                            f"Loss is not finite. Stopping. "
                            f"(step {step}, loss {loss}; detection is "
                            f"windowed — up to {lag} "
                            f"optimizer steps may have run past the first "
                            f"bad one; --debug pins the window to 1 for "
                            f"step-exact detection)")
                    self.writer.scalars(step + 1, **scalars)

    def _dump_nan_batch(self, step: int, arrays) -> None:
        """--debug: persist the batch that produced a non-finite loss
        (the reference wires tfdbg's has_inf_or_nan filter here,
        run_summarization.py:216-218)."""
        if not self.hps.debug or arrays is None:
            return
        path = os.path.join(self.train_dir, f"nan_batch_step{step}.npz")
        try:
            os.makedirs(self.train_dir, exist_ok=True)
            np.savez(path, **{k: np.asarray(v) for k, v in arrays.items()})
            log.error("non-finite loss at step %d; offending batch "
                      "dumped to %s", step, path)
        except Exception:  # the watchdog error must still propagate
            self._c_dump_errors.inc()
            log.exception("failed to dump NaN batch")

    def _recover(self, step: int) -> bool:
        """Armed divergence handling for one non-finite dispatch.

        Returns True when the run can continue (the offending dispatch
        was discarded; ``self.state`` is the state to resume from) and
        False when the skip AND rollback budgets are exhausted — the
        caller raises NanLossError.
        """
        rec = self._recovery
        action = rec.next_action()
        if action == "skip":
            rec.take_skip()
            log.warning(
                "non-finite loss at step %d: skipping the batch "
                "(%d consecutive skips left before rollback)",
                step, rec.skips_left)
            return True
        if action == "rollback":
            restored = rec.take_rollback()
            # the post-mortem moment: the frames BEFORE this rollback are
            # what "what did the last N steps look like?" asks about
            flightrec.trigger(self._obs, "nan_rollback", step=step,
                              rollbacks_left=rec.rollbacks_left)
            self.state = restored
            # the LR cut changes the step function: rebuild and drop the
            # multi-step cache (both re-jit; a rollback is rare enough
            # that the recompile is noise)
            self._step_fn = self._build_step_fn()
            self._multi_step_cache.clear()
            log.warning(
                "non-finite loss at step %d: rolled back to step %d with "
                "lr scale %.3g (%d rollbacks left)",
                step, int(np.asarray(restored.step)), rec.lr_scale,
                rec.rollbacks_left)
            return True
        return False

    def _train_steps(self, limit, last_ckpt, profile_dir, profile_start,
                     profile_stop, prefetcher, multihost) -> TrainState:
        profiling = False
        # multihost + checkpointer guarantees checkpoint_steps > 0 (the
        # hard guard in _train_loop); an explicit step cadence also wins
        # on single-host, else the wall-clock checkpoint_secs cadence
        # below applies
        checkpoint_steps = self.checkpoint_steps
        flush_every = max(self.metrics_every, 1)
        # metrics stay on device until flushed; keeping the (tiny) input
        # arrays alongside lets --debug dump the exact offending batch
        # (--debug forces steps_per_dispatch=1, so arrays are per-step)
        pending = []  # [(first_step, n_steps, device_metrics, arrays)]
        pending_steps = 0
        window_t0 = time.monotonic()
        # ONE device sync to learn the resume step; from here the counter
        # is tracked host-side (+n per dispatch) so the loop never blocks
        # on state.step and dispatch can run ahead of the device
        step = int(self.state.step)
        profile_done = False  # one-shot: never restart a finished trace
        exhausted = False
        while not exhausted:
            # trainer-loop heartbeat for /healthz (obs/http.py): one beat
            # per dispatch; 3x the shared period of silence — a wedged
            # input pipeline, a hung collective — marks the loop
            # degraded (LOOP_HEARTBEAT_PERIOD carries the
            # compile/checkpoint-tolerance rationale)
            obs_http.heartbeat(self._obs, "train/loop",
                               period=obs_http.LOOP_HEARTBEAT_PERIOD)
            if limit and step >= limit:
                break
            # per-round wall bracket (obs/profile.py, ISSUE 16): the
            # sub-phases below sum toward it, and the gap is the loop's
            # unattributed overhead (stacking, bookkeeping)
            with self._prof.wall("train/round"):
                # k batches per dispatch (steps_per_dispatch), clipped to the
                # remaining step budget so the limit stays exact
                k = self.steps_per_dispatch
                if limit:
                    k = min(k, limit - step)
                items = []
                t_wait = time.perf_counter()
                with self._prof.phase("train/host_wait"):
                    while len(items) < k:
                        item = prefetcher.next_batch()
                        if item is None:
                            exhausted = True
                            break
                        items.append(item)
                # host-wait: time the loop spent blocked on the input side
                # while the device sat idle (dispatch itself is async)
                self._m_host_wait.observe(time.perf_counter() - t_wait)
                if exhausted and (multihost and (limit == 0 or step + len(items)
                                                 < limit)):
                    raise RuntimeError(
                        f"batcher exhausted at step {step + len(items)} before "
                        f"the num_steps={limit} limit on a multi-host run; "
                        f"other hosts may still be issuing collectives — "
                        f"aborting instead of desyncing")
                if not items:
                    log.info("batcher exhausted; stopping training at step %d",
                             step)
                    break
                if profile_dir and not profiling and not profile_done \
                        and step >= profile_start:
                    self._flush_metrics(pending, time.monotonic() - window_t0)
                    pending = []
                    pending_steps = 0
                    jax.profiler.start_trace(profile_dir)
                    profiling = True
                    window_t0 = time.monotonic()
                    # the capture's opening edge in the profiler ledger
                    # (ISSUE 16): /profile names the step range a trace
                    # covers without grepping logs
                    self._prof.note("profiler_capture", dir=str(profile_dir),
                                    start_step=profile_start,
                                    stop_step=profile_stop)
                    log.info("profiler trace started -> %s", profile_dir)
                n = len(items)
                try:
                    with self._prof.phase("train/step_dispatch") as ph:
                        if n == 1:
                            _, arrays = items[0]
                            new_state, metrics = self._step_fn(self.state, arrays)
                        else:
                            # stack on device: k tiny int/float batch arrays gain
                            # a leading scan axis (bytes ~ k x the batch, trivial
                            # next to one dispatch round trip)
                            arrays = jax.tree_util.tree_map(
                                lambda *xs: jnp.stack(xs),
                                *[a for _, a in items])
                            new_state, metrics = self._multi_step(n)(
                                self.state, arrays)
                            arrays = None
                except FloatingPointError as e:
                    # jax_debug_nans (--debug, which pins n=1) raises inside
                    # the step with the op-level location; still dump the
                    # offending batch and surface the watchdog error type
                    self._c_nan.inc()
                    self._dump_nan_batch(step, arrays)
                    flightrec.trigger(self._obs, "train_nan", step=step)
                    if self._recovery is not None:
                        # the step never completed, so self.state is still
                        # the pre-dispatch state — skip/rollback from it
                        if self._recover(step):
                            # recovery path, not the per-step path: one sync
                            # to learn the resume step
                            step = int(np.asarray(self.state.step))  # tslint: disable=TS002
                            continue
                        raise NanLossError(
                            f"Loss is not finite and divergence recovery is "
                            f"exhausted. Stopping. (step {step}; "
                            f"jax_debug_nans trace above)") from e
                    raise NonFiniteLossError(
                        f"Loss is not finite. Stopping. (step {step}; "
                        f"jax_debug_nans trace above)") from e
                # dispatch-submit time (async under jax: device compute
                # overlaps with the host loop; the blocking D2H fetches are
                # the metrics-flush phase, not this one)
                self._prof.observe_dispatch("train/step_dispatch", "step",
                                            ph.dt)
                injected = self._faults.fire("train.step_nan")
                if self._recovery is not None:
                    # armed: one D2H metrics sync per dispatch — poisoned
                    # state must never outlive the dispatch that made it (the
                    # documented cost of arming, config.py nan_skip_steps)
                    fetched = jax.device_get(metrics)  # tslint: disable=TS002
                    finite = bool(np.all(np.isfinite(np.asarray(fetched.loss))))  # tslint: disable=TS002 — host data
                    if injected or not finite:
                        self._c_nan.inc()
                        self._dump_nan_batch(step, arrays)
                        flightrec.trigger(self._obs, "train_nan", step=step,
                                          injected=bool(injected))
                        # new_state is discarded; self.state (pre-dispatch,
                        # never donated when armed) remains the live params
                        if self._recover(step):
                            step = int(np.asarray(self.state.step))  # tslint: disable=TS002
                            continue
                        raise NanLossError(
                            f"Loss is not finite and divergence recovery is "
                            f"exhausted. Stopping. (step {step}"
                            f"{'; injected train.step_nan' if injected else ''})")
                    self.state = new_state
                    self._recovery.note_good(new_state)
                    metrics = fetched  # flush below reuses the fetched copy
                else:
                    # the dispatch itself completed: publish its state BEFORE
                    # any injected raise, so self.state never points at
                    # buffers the donated step already consumed (an on-error
                    # handler may still save it)
                    self.state = new_state
                    if injected:
                        self._c_nan.inc()
                        flightrec.trigger(self._obs, "train_nan", step=step,
                                          injected=True)
                        raise NonFiniteLossError(
                            f"injected train.step_nan fault at step {step} "
                            f"(divergence recovery unarmed: nan_skip_steps and "
                            f"nan_max_rollbacks are 0)")
                pending.append((step, n, metrics,
                                arrays if self.hps.debug else None))
                prev_step = step
                step += n
                pending_steps += n
                self._c_steps.inc(n)
                self._c_examples.inc(n * self.hps.batch_size)
                if pending_steps >= flush_every or self._recovery is not None:
                    self._flush_metrics(pending, time.monotonic() - window_t0)
                    pending = []
                    pending_steps = 0
                    window_t0 = time.monotonic()
                if profiling and step > profile_stop:
                    # the finalize edge gets its own span so one capture is
                    # one linkable event in events.jsonl (trace_summary.py
                    # lanes show the trace window next to the step spans)
                    with obs.spans.span(self._obs, "train/profiler_capture",
                                        parent=self._trace,
                                        start_step=profile_start,
                                        stop_step=profile_stop):
                        jax.profiler.stop_trace()
                    profiling = False
                    profile_done = True
                    log.info("profiler trace written to %s", profile_dir)
                if self.checkpointer is not None:
                    if checkpoint_steps > 0:
                        # crossed a cadence boundary this dispatch — identical
                        # arithmetic on every host, so saves stay collective
                        # even when k does not divide checkpoint_steps
                        due = (step // checkpoint_steps
                               ) != (prev_step // checkpoint_steps)
                    else:
                        due = time.monotonic() - last_ckpt >= self.checkpoint_secs
                    if due:
                        # the save fetches state anyway; fold the metrics
                        # flush into the same sync point
                        self._flush_metrics(pending, time.monotonic() - window_t0)
                        pending = []
                        pending_steps = 0
                        with self._prof.phase("train/checkpoint"):
                            self.checkpointer.save(self.state)
                        last_ckpt = time.monotonic()
                        window_t0 = time.monotonic()
        self._flush_metrics(pending, time.monotonic() - window_t0)
        if profiling:
            jax.profiler.stop_trace()
        if self.checkpointer is not None:
            self.checkpointer.save(self.state)
        return self.state


class Evaluator:
    """Eval loop with running-average loss + best-model hook
    (run_summarization.py:247-292)."""

    def __init__(self, hps: HParams, vsize: int, batcher: Any,
                 eval_dir: Optional[str] = None,
                 best_saver: Optional[Callable[[PyTree, float, int], None]] = None):
        self.hps = hps
        self.batcher = batcher
        self.eval_dir = eval_dir or os.path.join(
            hps.log_root or ".", hps.exp_name or "exp", "eval")
        self._obs = obs.registry_for(hps)
        self._m_eval_batch = self._obs.histogram("train/eval_batch_seconds")
        self._c_eval_batches = self._obs.counter("train/eval_batches_total")
        self.writer = SummaryWriter(
            self.eval_dir,
            flush_every=getattr(hps, "summary_flush_every", 1),
            registry=self._obs)
        self.best_saver = best_saver
        self.running_avg_loss = 0.0
        self.best_loss: Optional[float] = None
        self._shard_batch: Optional[Callable] = None
        self._mesh_plan = None
        if hps.dp * hps.tp * hps.sp > 1:  # same auto-mesh rule as Trainer
            from textsummarization_on_flink_tpu.parallel import mesh as mesh_lib

            self._mesh_plan = mesh_lib.make_mesh(hps)
            if jax.process_count() > 1:  # same per-host-shard rule as Trainer
                self._shard_batch = mesh_lib.make_host_local_transfer(
                    self._mesh_plan, hps.batch_size, label="eval")
            else:
                self._shard_batch = functools.partial(
                    mesh_lib.shard_batch, self._mesh_plan)
            self._eval_fn = None  # built lazily per params structure
        else:
            self._eval_fn = jax.jit(make_eval_step(hps))

    def run(self, params: PyTree, step: int, max_batches: int = 0) -> float:
        """Evaluate batches (all, or max_batches); returns running avg loss."""
        n = 0
        while True:
            batch = self.batcher.next_batch()
            if batch is None:
                break
            t0 = time.monotonic()
            arrays = batch.as_arrays()
            if self._shard_batch is not None:
                arrays = self._shard_batch(arrays)
            if self._eval_fn is None:  # mesh path: build for THIS params
                from textsummarization_on_flink_tpu.parallel import (
                    mesh as mesh_lib,
                )

                mesh_lib.validate_divisibility(self.hps, params)
                self._eval_fn = mesh_lib.make_sharded_eval_step(
                    self._mesh_plan, params=params)
            metrics = self._eval_fn(params, arrays)
            loss = float(metrics.total_loss if self.hps.coverage else metrics.loss)
            self._m_eval_batch.observe(time.monotonic() - t0)
            self._c_eval_batches.inc()
            log.info("seconds for eval batch: %.3f  loss: %f",
                     time.monotonic() - t0, loss)
            if not np.isfinite(loss):
                raise NonFiniteLossError("Eval loss is not finite.")
            self.running_avg_loss = calc_running_avg_loss(
                loss, self.running_avg_loss)
            self.writer.scalars(step, eval_loss=loss,
                                running_avg_loss=self.running_avg_loss)
            # best-model check PER eval iteration, inside the loop — the
            # reference saves whenever the smoothed loss improves after
            # each eval step (run_summarization.py:281-292), not once per
            # evaluation session
            if self.best_loss is None or self.running_avg_loss < self.best_loss:
                log.info("Found new best model with %.3f running_avg_loss. "
                         "Saving...", self.running_avg_loss)
                if self.best_saver is not None:
                    self.best_saver(params, self.running_avg_loss, step)
                self.best_loss = self.running_avg_loss
            n += 1
            if max_batches and n >= max_batches:
                break
        return self.running_avg_loss
