"""Checkpoint save/restore with the reference's full lifecycle semantics.

Rebuilds the reference checkpoint story (SURVEY.md §5.4) without TF:

  * 3-checkpoint retention + index file — `Saver(max_to_keep=3)` +
    the `checkpoint` latest-file protocol
    (/root/reference/src/main/python/pointer-generator/run_summarization.py:192,
    train.py:68).
  * best-model track with its own `checkpoint_best` index
    (run_summarization.py:250-292).
  * `load_ckpt` retry loop — decoders wait for trainers to produce a first
    checkpoint (util.py:29-41: infinite 10s retries).
  * checkpoint surgery: `convert_to_coverage_model`
    (run_summarization.py:157-178) and `restore_best_model`
    (run_summarization.py:132-154, which drops Adagrad accumulators).

Format: one ``.npz`` per checkpoint holding every leaf of the TrainState
pytree under its slash-joined key path (``params/decoder/attention/W_h``,
``opt_state/accumulators/...``, ``step``), plus a small JSON sidecar of
hparams for provenance.  Arrays are gathered to host before writing
(multi-host callers save on the chief only, parallel/distributed.is_chief).

Mesh story (ISSUE 8): a sharded TrainState saves through the same path —
the host-local gather in ``state_to_arrays`` assembles full arrays from
whatever layout the sharding registry (parallel/sharding.py) placed them
in, so checkpoints are mesh-shape-agnostic; ``restore_sharded`` places a
restored state onto ANY mesh against the registry specs (save at
dp4 x tp2, resume at dp2 x tp2, bit-identical after gather).
"""

from __future__ import annotations

import glob
import hashlib
import json
import logging
import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.resilience import faultinject
from textsummarization_on_flink_tpu.resilience.errors import (
    CheckpointCorruptError,
)
from textsummarization_on_flink_tpu.train import optim
from textsummarization_on_flink_tpu.train.trainer import TrainState

log = logging.getLogger(__name__)

PyTree = Any

CKPT_PREFIX = "model.ckpt"
INDEX_FILE = "checkpoint"  # latest-pointer file, tf.train.Saver protocol
BEST_INDEX_FILE = "checkpoint_best"
MANIFEST_SUFFIX = ".sum"  # checksum manifest sidecar (RESILIENCE.md)


# --------------------------------------------------------------------------
# Pytree <-> flat dict
# --------------------------------------------------------------------------

def _flatten(tree: PyTree, prefix: str = "") -> Dict[str, np.ndarray]:
    """Flatten nested dicts/NamedTuples to slash-joined keys."""
    out: Dict[str, np.ndarray] = {}
    tree = jax.device_get(tree)  # one batched D2H transfer, not per-leaf

    def rec(node: Any, path: str) -> None:
        if isinstance(node, dict):
            for k, v in node.items():
                rec(v, f"{path}/{k}" if path else str(k))
        elif hasattr(node, "_fields"):  # NamedTuple
            for k in node._fields:
                rec(getattr(node, k), f"{path}/{k}" if path else str(k))
        elif isinstance(node, (list, tuple)):  # e.g. transformer layer lists
            for i, v in enumerate(node):
                rec(v, f"{path}/{i}" if path else str(i))
        else:
            arr = np.asarray(node)
            if arr.dtype == jnp.bfloat16:
                # npz silently degrades ml_dtypes bf16 to a raw void
                # dtype on round trip; widen losslessly to f32 here and
                # let trainer.cast_opt_state re-narrow on resume
                arr = arr.astype(np.float32)
            out[path] = arr

    rec(tree, prefix)
    return out


def _listify(node: Any) -> Any:
    """Turn {'0': .., '1': ..} dicts (flattened lists) back into lists."""
    if isinstance(node, dict):
        node = {k: _listify(v) for k, v in node.items()}
        if node and all(k.isdigit() for k in node) \
                and sorted(int(k) for k in node) == list(range(len(node))):
            return [node[str(i)] for i in range(len(node))]
    return node


def _unflatten_dicts(flat: Dict[str, np.ndarray]) -> Dict[str, Any]:
    """Rebuild a nested tree from slash-joined keys (lists restored from
    their integer-key segments)."""
    root: Dict[str, Any] = {}
    for key, val in flat.items():
        parts = key.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    return _listify(root)


def state_to_arrays(state: TrainState) -> Dict[str, np.ndarray]:
    if jax.process_count() > 1:
        # tp/sp shards may live on other hosts' devices; a bare device_get
        # raises on non-addressable arrays. All-gather the full values
        # first (every host participates; only the chief writes).
        from jax.experimental import multihost_utils

        state = multihost_utils.process_allgather(state, tiled=True)
    return _flatten(state)


def arrays_to_state(flat: Dict[str, np.ndarray]) -> TrainState:
    tree = _unflatten_dicts(flat)
    step = tree.get("step", np.zeros((), np.int32))
    params = tree["params"]
    acc = tree.get("opt_state", {}).get("accumulators")
    if acc is None:
        acc = jax.tree_util.tree_map(lambda p: np.zeros_like(p), params)
    return TrainState(params=params,
                      opt_state=optim.AdagradState(accumulators=acc),
                      step=np.asarray(step, np.int32))


# --------------------------------------------------------------------------
# Raw file IO
# --------------------------------------------------------------------------

def content_fingerprint(tree: PyTree) -> str:
    """Content fingerprint of one params pytree: sha256 over every
    leaf's bytes in deterministic (flattened-name) order, truncated to
    16 hex chars.  Two trees collide only if they are byte-identical.

    The ONE fingerprint scheme (ISSUE 12/14): the distillation
    teacher sidecar (train/distill.teacher_fingerprint) and the serve
    layer's summary-cache key (decode/decoder.params_fingerprint,
    SERVING.md "Front door") both resolve through here, so the two can
    never drift — a draft checkpoint verified against a teacher and a
    cache entry keyed on a snapshot mean the same bytes."""
    flat = _flatten(tree)
    h = hashlib.sha256()
    for name in sorted(flat):
        h.update(name.encode("utf-8"))
        h.update(np.ascontiguousarray(flat[name]).tobytes())
    return h.hexdigest()[:16]


def _file_sha256(path: str) -> Tuple[str, int]:
    h = hashlib.sha256()
    size = 0
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
            size += len(block)
    return h.hexdigest(), size


def save_arrays(path: str, flat: Dict[str, np.ndarray]) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **flat)
    # checksum manifest (RESILIENCE.md): hashed from the tmp file BEFORE
    # publish, so a manifest can never describe a file it didn't see;
    # published after the npz so readers either find a verifiable pair
    # or (crash window) a checkpoint without a manifest — never a
    # manifest for a missing/partial checkpoint
    digest, size = _file_sha256(tmp)
    try:
        # an overwrite (e.g. training re-reaching a step after a NaN
        # rollback) must not leave the OLD manifest describing the NEW
        # bytes during the publish window — drop it first so readers see
        # manifest-less (loadable unverified), never mismatched
        os.remove(path + MANIFEST_SUFFIX)
    except OSError:
        pass
    os.replace(tmp, path)  # atomic publish; readers never see partial files
    mtmp = path + MANIFEST_SUFFIX + ".tmp"
    with open(mtmp, "w", encoding="utf-8") as f:
        json.dump({"algo": "sha256", "hexdigest": digest, "bytes": size,
                   "file": os.path.basename(path)}, f)
    os.replace(mtmp, path + MANIFEST_SUFFIX)


def load_arrays(path: str) -> Dict[str, np.ndarray]:
    with np.load(path, allow_pickle=False) as z:
        return {k: z[k] for k in z.files}


def verify_manifest(path: str) -> bool:
    """Check `path` against its checksum manifest.

    Returns True when the manifest exists and matches, False when there
    is no manifest (pre-manifest checkpoint: nothing to verify against).
    Raises CheckpointCorruptError on a mismatch or unreadable manifest.
    """
    mpath = path + MANIFEST_SUFFIX
    if not os.path.exists(mpath):
        return False
    try:
        with open(mpath, "r", encoding="utf-8") as f:
            manifest = json.load(f)
        want = manifest["hexdigest"]
        want_bytes = int(manifest.get("bytes", -1))
    except (OSError, ValueError, KeyError) as e:
        raise CheckpointCorruptError(
            f"unreadable checksum manifest {mpath}") from e
    got, size = _file_sha256(path)
    if got != want or (want_bytes >= 0 and size != want_bytes):
        raise CheckpointCorruptError(
            f"checkpoint {path} failed checksum verification "
            f"(manifest {want[:12]}.../{want_bytes}B, "
            f"file {got[:12]}.../{size}B)")
    return True


def load_arrays_verified(path: str,
                         faults: Optional[Any] = None,
                         ) -> Dict[str, np.ndarray]:
    """Checksum-verify (when a manifest exists) then load.  A zip/npz
    decode failure is normalized to CheckpointCorruptError so every
    corruption class routes through the same fallback."""
    plan = faults if faults is not None else faultinject.plan()
    if plan.fire("ckpt.load"):
        raise CheckpointCorruptError(f"injected ckpt.load fault for {path}")
    verify_manifest(path)
    try:
        return load_arrays(path)
    except (ValueError, OSError, KeyError) as e:
        # manifest matched (or was absent) but the payload won't decode
        raise CheckpointCorruptError(
            f"checkpoint {path} failed to decode: {e}") from e


def _write_index(directory: str, ckpt_path: str, index_file: str) -> None:
    tmp = os.path.join(directory, index_file + ".tmp")
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump({"model_checkpoint_path": os.path.basename(ckpt_path)}, f)
    os.replace(tmp, os.path.join(directory, index_file))


def latest_checkpoint(directory: str, index_file: str = INDEX_FILE,
                      ) -> Optional[str]:
    """Resolve the newest checkpoint path via the index file (falling back
    to a directory scan, like tf.train.latest_checkpoint)."""
    idx = os.path.join(directory, index_file)
    if os.path.exists(idx):
        try:
            with open(idx, "r", encoding="utf-8") as f:
                name = json.load(f)["model_checkpoint_path"]
            path = name if os.path.isabs(name) else os.path.join(directory, name)
            if os.path.exists(path):
                return path
        except (json.JSONDecodeError, KeyError, OSError):
            log.warning("unreadable checkpoint index %s; rescanning", idx)
    prefix = "bestmodel" if index_file == BEST_INDEX_FILE else CKPT_PREFIX
    pattern = os.path.join(directory, f"{prefix}-*.npz")
    found = sorted(glob.glob(pattern), key=_ckpt_step)
    return found[-1] if found else None


def _ckpt_step(path: str) -> Tuple[int, int]:
    """Sort key: (step, is_surgery).  Surgery outputs
    (`-<N>_cov_init.npz`, `-<N>_restored.npz`) carry their source step and
    sort *after* the plain checkpoint of the same step (they are newer)."""
    m = re.search(r"-(\d+)(_[a-z_]+)?\.npz$", path)
    if not m:
        return (-1, 0)
    return (int(m.group(1)), 1 if m.group(2) else 0)


def checkpoint_candidates(directory: str, index_file: str = INDEX_FILE,
                          ) -> List[str]:
    """Checkpoint paths newest-first: the index-resolved latest, then
    every on-disk sibling in descending step order (the corruption
    fallback chain, RESILIENCE.md)."""
    prefix = "bestmodel" if index_file == BEST_INDEX_FILE else CKPT_PREFIX
    pattern = os.path.join(directory, f"{prefix}-*.npz")
    found = sorted(glob.glob(pattern), key=_ckpt_step, reverse=True)
    latest = latest_checkpoint(directory, index_file)
    if latest is not None and latest in found:
        found.remove(latest)
        found.insert(0, latest)
    elif latest is not None:
        found.insert(0, latest)
    return found


def load_ckpt(directory: str, index_file: str = INDEX_FILE,
              max_retries: Optional[int] = None, retry_secs: float = 10.0,
              faults: Optional[Any] = None,
              ) -> Tuple[str, Dict[str, np.ndarray]]:
    """Load the newest loadable checkpoint, retrying until one appears
    (util.py:29-41: infinite 10s retry by default).

    Resilience (ISSUE 2): each attempt walks the candidate chain newest
    to oldest, checksum-verifying via the manifest — a corrupted latest
    checkpoint falls back to the next-older one instead of crashing
    (``resilience/ckpt_fallbacks_total``).  The wait loop itself is
    observable: ``ckpt/load_retries_total`` counts sleeps and
    ``ckpt/load_wait_seconds`` gauges the cumulative wait, so a decoder
    stuck waiting on a trainer is visible rather than silent.
    """
    attempt = 0
    waited = 0.0
    c_retries = obs.counter("ckpt/load_retries_total")
    c_fallbacks = obs.counter("resilience/ckpt_fallbacks_total")
    g_wait = obs.gauge("ckpt/load_wait_seconds")
    while True:
        for i, path in enumerate(checkpoint_candidates(directory, index_file)):
            try:
                flat = load_arrays_verified(path, faults=faults)
            except CheckpointCorruptError as e:
                c_fallbacks.inc()
                log.warning("checkpoint %s unusable (%s); falling back to "
                            "the next-older checkpoint", path, e)
                continue
            except OSError as e:  # raced with retention cleanup
                log.info("Failed to load checkpoint from %s: %s", path, e)
                continue
            if i > 0:
                log.warning("loaded fallback checkpoint %s (newer "
                            "candidates were corrupt)", path)
            return path, flat
        attempt += 1
        if max_retries is not None and attempt > max_retries:
            raise FileNotFoundError(
                f"no loadable checkpoint in {directory} after "
                f"{max_retries} retries")
        log.info("Failed to load checkpoint from %s. Sleeping %.0f secs...",
                 directory, retry_secs)
        c_retries.inc()
        time.sleep(retry_secs)
        waited += retry_secs
        g_wait.set(waited)


# --------------------------------------------------------------------------
# Checkpointer / BestModelSaver
# --------------------------------------------------------------------------

class Checkpointer:
    """Rolling-retention trainer checkpoints (Saver(max_to_keep=3) parity)."""

    def __init__(self, directory: str, max_to_keep: int = 3,
                 hps: Optional[HParams] = None):
        self.directory = directory
        self.max_to_keep = max_to_keep
        self.hps = hps
        # a per-job fault plan (hps.faults) is resolved ONCE so its RNG
        # streams and fire budgets persist across restore() calls — a
        # "fails exactly N times then heals" spec must not reset per
        # call.  The process default stays dynamic (resolved per use) so
        # TS_FAULTS / use_plan() contexts keep routing.
        self._job_faults = (
            faultinject.plan_for(hps)
            if hps is not None and getattr(hps, "faults", "") else None)
        os.makedirs(directory, exist_ok=True)
        # the provenance sidecar is written on the first save(), not here:
        # consulting is_chief() would force JAX backend init inside a
        # filesystem-only constructor (it would take the chip, and
        # before jax.distributed.initialize every host believes it is
        # process 0)
        self._sidecar_pending = hps is not None

    def _write_sidecar(self) -> None:
        # written once, atomically — chief-only (every host constructs a
        # Checkpointer on a shared dir; a shared tmp name would race),
        # pid-suffixed as defense
        tmp = os.path.join(self.directory, f"hparams.json.tmp{os.getpid()}")
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(self.hps.to_json())
        os.replace(tmp, os.path.join(self.directory, "hparams.json"))
        self._sidecar_pending = False

    def save(self, state: TrainState) -> str:
        """Multi-host: EVERY host must call this (the shard gather inside
        state_to_arrays is collective); only the chief touches the
        filesystem."""
        from textsummarization_on_flink_tpu.parallel import distributed

        reg = obs.registry_for(self.hps)
        t0 = time.perf_counter()
        with obs.spans.span(reg, "checkpoint/save"):
            flat = state_to_arrays(state)  # collective on multi-host
            step = int(np.asarray(flat.get("step", 0)))
            path = os.path.join(self.directory, f"{CKPT_PREFIX}-{step}.npz")
            if not distributed.is_chief():
                return path
            if self._sidecar_pending:
                self._write_sidecar()
            save_arrays(path, flat)
            _write_index(self.directory, path, INDEX_FILE)
            self._retain()
        reg.histogram("checkpoint/save_seconds").observe(
            time.perf_counter() - t0)
        reg.counter("checkpoint/saves_total").inc()
        try:
            reg.counter("checkpoint/save_bytes_total").inc(
                os.path.getsize(path))
        except OSError:  # pragma: no cover - raced with retention/cleanup
            pass
        log.info("saved checkpoint %s", path)
        return path

    def _retain(self) -> None:
        ckpts = sorted(
            glob.glob(os.path.join(self.directory, f"{CKPT_PREFIX}-*.npz")),
            key=_ckpt_step)
        for old in ckpts[: max(0, len(ckpts) - self.max_to_keep)]:
            try:
                os.remove(old)
                log.info("removed old checkpoint %s", old)
            except OSError:
                pass
            try:
                os.remove(old + MANIFEST_SUFFIX)
            except OSError:
                pass

    def _load_with_fallback(
            self, reg: obs.Registry,
    ) -> Tuple[Optional[str], Optional[Dict[str, np.ndarray]]]:
        """(path, arrays) of the newest loadable checkpoint, checksum-
        verified, falling back over corrupt candidates (RESILIENCE.md);
        (None, None) when the directory holds no loadable checkpoint."""
        faults = (self._job_faults if self._job_faults is not None
                  else faultinject.plan())
        for path in checkpoint_candidates(self.directory):
            try:
                return path, load_arrays_verified(path, faults=faults)
            except (CheckpointCorruptError, OSError) as e:
                reg.counter("resilience/ckpt_fallbacks_total").inc()
                log.warning("checkpoint %s unusable (%s); falling back to "
                            "the next-older checkpoint", path, e)
        return None, None

    def restore(self, path: Optional[str] = None) -> Optional[TrainState]:
        reg = obs.registry_for(self.hps)
        if path is None:
            path, flat = self._load_with_fallback(reg)
            if flat is None:
                return None
        else:
            # explicit path: verification failure surfaces to the caller
            # (they asked for THIS checkpoint, silently substituting
            # another would be wrong)
            flat = load_arrays_verified(
                path,
                faults=(self._job_faults if self._job_faults is not None
                        else faultinject.plan()))
        t0 = time.perf_counter()
        with obs.spans.span(reg, "checkpoint/restore"):
            state = arrays_to_state(flat)
        reg.histogram("checkpoint/restore_seconds").observe(
            time.perf_counter() - t0)
        reg.counter("checkpoint/restores_total").inc()
        try:
            reg.counter("checkpoint/restore_bytes_total").inc(
                os.path.getsize(path))
        except OSError:  # pragma: no cover
            pass
        return state

    def restore_sharded(self, plan: Any,
                        path: Optional[str] = None,
                        ) -> Optional[TrainState]:
        """Restore and place onto `plan`'s mesh against the sharding
        registry's specs (ISSUE 8: one mesh story).

        Checkpoints are mesh-shape-agnostic: save() gathers shards to
        full host arrays (state_to_arrays), so a state saved from a
        dp4 x tp2 mesh restores onto dp2 x tp2 — or any other shape the
        registry can lay it out on — with bit-identical values after a
        gather.  When the registry's hps store the Adagrad accumulators
        in bf16, they are re-narrowed BEFORE placement (npz cannot hold
        bf16, so save() widened them losslessly to f32) — the same
        widen/narrow round trip the Trainer applies on resume.
        """
        state = self.restore(path)
        if state is None:
            return None
        from textsummarization_on_flink_tpu.train import (
            trainer as trainer_lib,
        )

        registry = plan.registry
        state = trainer_lib.cast_opt_state(registry.hps, state)
        return registry.shard_state(state)


class BestModelSaver:
    """Eval-side best-model track (run_summarization.py:250-292): keeps ONE
    `bestmodel-<step>.npz` under eval_dir, indexed by `checkpoint_best`."""

    def __init__(self, eval_dir: str):
        self.eval_dir = eval_dir
        os.makedirs(eval_dir, exist_ok=True)

    def __call__(self, params: PyTree, running_avg_loss: float, step: int,
                 ) -> str:
        path = os.path.join(self.eval_dir, f"bestmodel-{step}.npz")
        old = glob.glob(os.path.join(self.eval_dir, "bestmodel-*.npz"))
        save_arrays(path, _flatten(params, "params"))
        _write_index(self.eval_dir, path, BEST_INDEX_FILE)
        for o in old:
            if o != path:
                try:
                    os.remove(o)
                except OSError:
                    pass
                try:
                    os.remove(o + MANIFEST_SUFFIX)
                except OSError:
                    pass
        log.info("saved best model (loss %.4f) to %s", running_avg_loss, path)
        return path


# --------------------------------------------------------------------------
# Checkpoint surgery
# --------------------------------------------------------------------------

def convert_to_coverage_model(train_dir: str, hps: HParams,
                              seed: int = 0, force: bool = False) -> str:
    """Add fresh coverage params to the latest non-coverage checkpoint and
    save it as `<ckpt>_cov_init` (run_summarization.py:157-178 semantics:
    restore non-coverage vars, init the new coverage vars, save-and-exit).

    Refuses to re-convert a checkpoint that is itself a coverage conversion
    (double invocation would overwrite trained coverage params with fresh
    noise); pass force=True to override.
    """
    from textsummarization_on_flink_tpu.models import pointer_generator as pg

    path = latest_checkpoint(train_dir)
    if path is None:
        raise FileNotFoundError(f"no checkpoint in {train_dir}")
    if "_cov_init" in os.path.basename(path) and not force:
        raise RuntimeError(
            f"latest checkpoint {path} is already a coverage conversion; "
            "re-converting would destroy trained coverage params "
            "(pass force=True to override)")
    state = arrays_to_state(load_arrays(path))
    if "attention" not in (state.params.get("decoder") or {}):
        raise ValueError(
            "coverage conversion applies to the pointer_generator family "
            "only — the transformer's coverage penalty has no parameters "
            "to add, set --coverage directly")
    new_params = pg.add_coverage_params(state.params,
                                        jax.random.PRNGKey(seed))
    # fresh accumulator only for the new variable (others keep history)
    new_acc = jax.tree_util.tree_map(lambda x: x, state.opt_state.accumulators)
    new_acc["decoder"]["attention"]["w_c"] = np.full_like(
        np.asarray(new_params["decoder"]["attention"]["w_c"]),
        hps.adagrad_init_acc)
    new_state = TrainState(params=new_params,
                           opt_state=optim.AdagradState(accumulators=new_acc),
                           step=state.step)
    out = path[: -len(".npz")] + "_cov_init.npz"
    save_arrays(out, state_to_arrays(new_state))
    _write_index(train_dir, out, INDEX_FILE)
    log.info("saved coverage-converted checkpoint %s", out)
    return out


def restore_best_model(eval_dir: str, train_dir: str, hps: HParams) -> str:
    """Copy the eval best model into the train dir with FRESH Adagrad
    accumulators (run_summarization.py:132-154 restores only non-Adagrad
    variables), saved as `model.ckpt-<step>_restored.npz`."""
    path = latest_checkpoint(eval_dir, BEST_INDEX_FILE)
    if path is None:
        raise FileNotFoundError(f"no best model in {eval_dir}")
    flat = load_arrays(path)
    params = _unflatten_dicts(flat)["params"]
    acc = jax.tree_util.tree_map(
        lambda p: np.full_like(p, hps.adagrad_init_acc), params)
    m = re.search(r"-(\d+)\.npz$", path)
    step = int(m.group(1)) if m else 0
    state = TrainState(params=params,
                       opt_state=optim.AdagradState(accumulators=acc),
                       step=np.asarray(step, np.int32))
    out = os.path.join(train_dir, f"{CKPT_PREFIX}-{step}_restored.npz")
    save_arrays(out, state_to_arrays(state))
    _write_index(train_dir, out, INDEX_FILE)
    log.info("restored best model %s -> %s", path, out)
    return out
