"""Device mesh + sharded step builders: SPMD data/tensor parallelism.

This replaces the reference's entire distributed stack — the TF1
parameter-server/worker cluster (`ClusterSpec`/`tf.train.Server`/
`replica_device_setter`, /root/reference/src/main/python/pointer-generator/
run_summarization.py:406-417), ZooKeeper coordination
(TFEstimator.java:50-51), and gRPC variable traffic — with a single SPMD
program over a `jax.sharding.Mesh`:

  * **dp** axis: batch sharding.  Gradients are all-reduced by XLA-inserted
    `psum` over ICI, replacing the reference's (scaffolded, never-exercised)
    async PS-style data parallelism (`worker_num`, HasClusterConfig.java:20-24).
  * **tp** axis: tensor parallelism for the big vocab matmuls — the
    `[H, vocab]` output projection (model.py:228-238) and the `[vocab, E]`
    embedding table are sharded over the vocab axis; XLA inserts the
    all-gather / reduce-scatter.
  * **sp** axis: context parallelism over the encoder sequence axis for the
    long-context configs (hidden 512, enc 800) — encoder states,
    attention energies, and coverage shard over T_enc; the per-step context
    reduction becomes a psum.  (The LSTM time scan itself is sequential, so
    sp shards the *attention/feature* tensors, which dominate memory at
    long T_enc.)

Layout decisions do NOT live here: every PartitionSpec comes from the
sharding-spec registry (parallel/sharding.py, ISSUE 8) — one declarative
role -> spec (+ wire dtype) table consumed by the step builders below,
the serving paths, the checkpointer, and bench alike.  The step builders
in this module construct no specs of their own (pinned by test).

There is no parameter server and no coordination store to configure: in a
multi-host deployment `jax.distributed.initialize()` (distributed.py) is
the rendezvous, and collectives ride ICI within a slice / DCN across
slices.

Everything here works identically on a virtual CPU mesh
(``--xla_force_host_platform_device_count=8``), which is how tests and the
driver's `dryrun_multichip` validate multi-chip behavior without hardware.
"""

from __future__ import annotations

import dataclasses
import logging
import warnings
from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: F401 — P re-exported for callers/tests

from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.parallel import sharding as sharding_lib
from textsummarization_on_flink_tpu.train import trainer as trainer_lib

PyTree = Any

log = logging.getLogger(__name__)

MESH_AXES = sharding_lib.MESH_AXES


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """A mesh plus the hps its sharding registry derives from."""

    mesh: Mesh
    hps: HParams

    @property
    def dp(self) -> int:
        return self.mesh.shape["dp"]

    @property
    def tp(self) -> int:
        return self.mesh.shape["tp"]

    @property
    def sp(self) -> int:
        return self.mesh.shape["sp"]

    @property
    def registry(self) -> sharding_lib.ShardingRegistry:
        return sharding_lib.registry_for(self)

    def named(self, spec: P) -> NamedSharding:
        return self.registry.named(spec)


def make_mesh(hps: HParams, devices: Optional[Sequence[jax.Device]] = None,
              ) -> MeshPlan:
    """Build the (dp, tp, sp) mesh.

    Axis sizes come from hps; when dp*tp*sp is smaller than the available
    device count the mesh uses a prefix subset (and logs it — raise your
    axis sizes to use the whole machine).  With all axes 1 this degrades
    gracefully to single-device.
    """
    devices = list(devices) if devices is not None else list(jax.devices())
    want = hps.dp * hps.tp * hps.sp
    if want > len(devices):
        raise ValueError(
            f"mesh needs dp*tp*sp={want} devices, have {len(devices)}")
    if want < len(devices):
        log.info("mesh uses %d of %d available devices (dp=%d tp=%d sp=%d)",
                 want, len(devices), hps.dp, hps.tp, hps.sp)
    grid = np.asarray(devices[:want]).reshape(hps.dp, hps.tp, hps.sp)
    return MeshPlan(mesh=Mesh(grid, MESH_AXES), hps=hps)


# --------------------------------------------------------------------------
# Registry delegates (public API preserved; the specs live in sharding.py)
# --------------------------------------------------------------------------

def param_pspecs(params: PyTree) -> PyTree:
    """PartitionSpec tree for a model-family parameter pytree (the
    registry's per-leaf param rule; see sharding.param_spec)."""
    return sharding_lib.param_specs(params)


def batch_pspec(name: str) -> P:
    return sharding_lib.batch_spec(name)


def batch_sharding(plan: MeshPlan) -> Dict[str, NamedSharding]:
    reg = plan.registry
    return reg.shardings(reg.batch_specs())


def state_pspecs(state: trainer_lib.TrainState) -> trainer_lib.TrainState:
    """PartitionSpecs for the full TrainState (registry state rule)."""
    return sharding_lib.state_specs(state)


def shard_train_state(plan: MeshPlan,
                      state: trainer_lib.TrainState) -> trainer_lib.TrainState:
    """Place a host-resident TrainState onto the mesh."""
    return plan.registry.shard_state(state)


def shard_batch(plan: MeshPlan, arrays: Dict[str, Any]) -> Dict[str, Any]:
    return plan.registry.shard_batch(arrays)


def param_shardings(plan: MeshPlan, params: Optional[PyTree] = None):
    """NamedSharding tree for a parameter pytree; pass `params` when its
    structure differs from a fresh init (e.g. TF1-imported trees)."""
    probe = params if params is not None else jax.eval_shape(
        lambda: trainer_lib.init_train_state(
            plan.hps, plan.hps.vocab_size, seed=0)).params
    return plan.registry.shardings(sharding_lib.param_specs(probe))


# --------------------------------------------------------------------------
# The unified sharded step
# --------------------------------------------------------------------------

def _with_mesh_context(plan: MeshPlan, fn):
    """Expose the plan's mesh to model code while the step traces, so
    mesh-aware ops (ring attention's shard_map) can bind to it."""
    from textsummarization_on_flink_tpu.parallel import ring_attention as ra

    def wrapped(*args):
        with ra.mesh_context(plan.mesh):
            return fn(*args)

    return wrapped


def _make_wire_grad_fn(plan: MeshPlan, reg: sharding_lib.ShardingRegistry,
                       param_spec_tree: PyTree):
    """(params, arrays) -> (grads, scalar losses) with the dp gradient
    all-reduce riding the wire in the registry's annotated dtype.

    Mechanism (ISSUE 8; sharding.py's module docstring): the batch
    regroups ``[B] -> [dp, B/dp]`` under a `P("dp", ...)` constraint,
    per-group grads come from ONE vmap'd jax.grad (each dp shard
    computes exactly its local rows), the stacked grads are cast to
    the wire dtype under a ``P("dp", *param_spec)`` constraint, and the
    group-axis sum is partitioned by XLA into the dp all-reduce at that
    dtype — spec-level wire annotation, collective inserted by the
    partitioner.  f32 is restored before clip/Adagrad; forward-internal
    tp collectives stay wherever GSPMD puts them, which is what makes
    this compose with dp x tp meshes.

    Requirements (validated in HParams.validate and here): sp == 1, and
    pointer_gen losses — their per-example normalization makes the
    mean of per-group means exactly the global mean, so the wire cast
    is the ONLY difference from the f32 step (parity pinned by test).
    """
    import jax.numpy as jnp

    hps = plan.hps
    if plan.sp > 1:
        raise ValueError(
            "grad_allreduce_dtype=bfloat16 supports dp x tp meshes "
            f"(sp=1), got sp={plan.sp}")
    if not hps.pointer_gen:
        raise ValueError(
            "grad_allreduce_dtype=bfloat16 requires pointer_gen losses "
            "(group-mean == global-mean); the baseline CE normalizes by "
            "the global token count")
    loss_fn = trainer_lib.make_loss_fn(hps)
    wire = reg.wire_dtype("grads")
    dp = plan.dp

    def grad_fn(params, arrays):
        def regroup(name, v):
            v = v.reshape((dp, v.shape[0] // dp) + v.shape[1:])
            return reg.constrain(v, reg.grouped_batch_spec(name))

        grouped = {k: regroup(k, v) for k, v in arrays.items()}

        def one_group(group_arrays):
            grads, out = jax.grad(
                lambda p: loss_fn(p, group_arrays),
                has_aux=True)(params)
            return grads, (out.loss, out.coverage_loss, out.total_loss)

        grads, scal = jax.vmap(one_group)(grouped)
        # THE lever: stacked per-group grads pinned to the registry's
        # stacked-grad spec in the wire dtype, so the group-axis sum
        # lowers to the dp all-reduce at that dtype; f32 restored
        # before any update math
        grads = jax.tree_util.tree_map(
            lambda g, s: reg.constrain(g.astype(wire),
                                       reg.stacked_grad_spec(s)),
            grads, param_spec_tree, is_leaf=lambda x: isinstance(x, P))
        grads = jax.tree_util.tree_map(
            lambda g: g.sum(axis=0).astype(jnp.float32) / dp, grads)
        return grads, tuple(jnp.mean(s) for s in scal)

    return grad_fn


def make_sharded_train_step(plan: MeshPlan, donate: bool = True,
                            state: Optional[trainer_lib.TrainState] = None):
    """THE sharded train step: one jitted program whose in/out shardings
    come from the sharding registry and whose body is the single
    trainer_lib.make_train_step body.

    Sharding is expressed entirely through registry specs — XLA inserts
    the dp-axis gradient psum, the tp-axis collectives around the vocab
    matmuls, and the sp-axis context reductions.  When the registry
    annotates a grad wire dtype (``--grad_allreduce_dtype=bfloat16``)
    the gradient computation swaps to the wire variant above — same
    step body, half the per-step dp collective bytes, now on any
    dp x tp mesh (the separate pure-dp shard_map builder is retired;
    see make_lowp_allreduce_train_step's shim).

    Pass `state` when its pytree structure differs from a fresh init
    (e.g. a TF1-imported non-coverage checkpoint has no
    decoder/attention/w_c leaf); specs are derived from the given tree
    so the jit's in_shardings structure matches.
    """
    hps = plan.hps
    reg = plan.registry
    probe = state if state is not None else jax.eval_shape(
        # structure only, nothing allocated
        lambda: trainer_lib.init_train_state(hps, hps.vocab_size, seed=0))
    grad_fn = None
    if reg.wire_dtype("grads") is not None:
        grad_fn = _make_wire_grad_fn(plan, reg,
                                     sharding_lib.param_specs(probe.params))
    step_fn = _with_mesh_context(
        plan, trainer_lib.make_train_step(hps, grad_fn=grad_fn))
    state_sh = reg.shardings(reg.state_specs(probe))
    del probe
    batch_sh = reg.shardings(reg.batch_specs())
    metric_sh = reg.shardings(reg.metric_specs())
    return jax.jit(
        step_fn,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, metric_sh),
        donate_argnums=(0,) if donate else (),
    )


def make_lowp_allreduce_train_step(
        plan: MeshPlan, donate: bool = True,
        state: Optional[trainer_lib.TrainState] = None):
    """DEPRECATED shim (ISSUE 8 satellite): the explicit-collective
    shard_map step this built is retired — the unified builder folds the
    bf16 gradient wire in as a registry-level dtype annotation and works
    on dp x tp meshes the shard_map step rejected.  Kept so existing
    callers resolve; delegates to make_sharded_train_step with the wire
    dtype forced on."""
    warnings.warn(
        "make_lowp_allreduce_train_step is deprecated: the unified "
        "make_sharded_train_step reads the grad wire dtype from the "
        "sharding registry (hps.grad_allreduce_dtype) and supports "
        "dp x tp meshes; call it directly",
        DeprecationWarning, stacklevel=2)
    hps = plan.hps
    if getattr(hps, "grad_allreduce_dtype", "float32") != "bfloat16":
        plan = dataclasses.replace(
            plan, hps=hps.replace(grad_allreduce_dtype="bfloat16"))
    return make_sharded_train_step(plan, donate=donate, state=state)


def make_sharded_eval_step(plan: MeshPlan, params: Optional[PyTree] = None):
    """Pass `params` when the tree structure differs from a fresh init
    (e.g. a TF1-imported checkpoint) so in_shardings match, mirroring
    make_sharded_train_step's `state` parameter."""
    hps = plan.hps
    reg = plan.registry
    eval_fn = _with_mesh_context(plan, trainer_lib.make_eval_step(hps))
    param_sh = param_shardings(plan, params)
    batch_sh = reg.shardings(reg.batch_specs())
    metric_sh = reg.shardings(reg.metric_specs())
    return jax.jit(eval_fn, in_shardings=(param_sh, batch_sh),
                   out_shardings=metric_sh)


def validate_divisibility(hps: HParams, params: Optional[PyTree] = None,
                          ) -> None:
    """Fail fast with actionable errors instead of opaque device_put
    shape complaints (the vocab file may hold fewer words than
    --vocab_size, so the ACTUAL embedding rows are what tp must divide)."""
    if hps.dp > 1 and hps.batch_size % hps.dp != 0:
        raise ValueError(f"data-parallel axis dp={hps.dp} must divide "
                         f"batch_size={hps.batch_size}")
    if hps.tp > 1 and params is not None:
        vsize_actual = params["embedding"].shape[0]
        if vsize_actual % hps.tp != 0:
            raise ValueError(
                f"tensor-parallel axis tp={hps.tp} must divide the actual "
                f"vocabulary size {vsize_actual} (the vocab file may hold "
                f"fewer words than --vocab_size); pick a dividing tp or "
                f"trim the vocab")
    if hps.sp > 1 and hps.max_enc_steps % hps.sp != 0:
        raise ValueError(f"sequence-parallel axis sp={hps.sp} must divide "
                         f"max_enc_steps={hps.max_enc_steps}")
    if hps.sp > 1 and hps.sp_attention == "ulysses" \
            and hps.num_heads % hps.sp != 0:
        raise ValueError(
            f"sp_attention=ulysses re-shards heads over sp: sp={hps.sp} "
            f"must divide num_heads={hps.num_heads}")
    if hps.tp > 1 and hps.model_family == "transformer":
        if hps.num_heads % hps.tp != 0:
            raise ValueError(
                f"tensor-parallel axis tp={hps.tp} must divide "
                f"num_heads={hps.num_heads} (Megatron head sharding)")
        if hps.ffn_width % hps.tp != 0:
            raise ValueError(f"tensor-parallel axis tp={hps.tp} must divide "
                             f"ffn_dim={hps.ffn_width}")
        if hps.sp_attention:
            raise ValueError(
                "sp_attention with tp>1 is not supported: the SP "
                "shard_map replicates the head axis, which would silently "
                "all-gather the Megatron-sharded q/k/v every layer — use "
                "sp-only attention (tp=1) or tp without sp_attention")


def make_sharded_beam_search(plan: MeshPlan,
                             params: Optional[PyTree] = None):
    """Multi-chip serving: beam-search decode with the article batch
    sharded over dp (each chip searches its own articles; beams stay
    chip-local, so there is zero cross-chip traffic during the decode
    loop — the ideal layout for throughput serving).

    Returns a jitted fn(params, arrays) -> BeamSearchOutput.  All
    shardings come from the registry (enc batch, params, beam output).
    """
    from textsummarization_on_flink_tpu.decode import beam_search

    hps = plan.hps
    reg = plan.registry
    param_sh = param_shardings(plan, params)
    batch_sh = reg.shardings(
        reg.batch_specs(sharding_lib.ENC_BATCH_NAMES))
    out_sh = reg.shardings(reg.beam_output_specs())

    def search(p, arrays):
        return beam_search._search_batch(p, hps, arrays)

    # mesh context so the encoder's sp attention engages in serving too
    # (a model trained with --sp_attention because [T,T] doesn't fit one
    # device must not fall back to full attention at decode time)
    search = _with_mesh_context(plan, search)
    return jax.jit(search, in_shardings=(param_sh, batch_sh),
                   out_shardings=out_sh)


def make_host_local_transfer(plan: MeshPlan, global_batch_size: int,
                             label: str = "train"):
    """Batch-transfer fn for one host of a multi-host run: validates this
    host's row count (batch_size/process_count) then assembles the global
    dp-sharded batch.  Shared by Trainer and Evaluator so the check and
    the error text cannot drift."""
    import jax

    nproc = jax.process_count()
    if global_batch_size % nproc != 0:
        raise ValueError(f"{label} batch_size={global_batch_size} must be "
                         f"divisible by process_count={nproc}")
    local_rows = global_batch_size // nproc

    def to_global(arrays: Dict[str, Any]) -> Dict[str, Any]:
        got = next(iter(arrays.values())).shape[0]
        if got != local_rows:
            raise ValueError(
                f"multi-host {label} batcher must yield {local_rows} "
                f"rows/host (global batch {global_batch_size} / {nproc} "
                f"hosts), got {got}")
        return global_batch_from_host_local(plan, arrays)

    return to_global


def global_batch_from_host_local(plan: MeshPlan,
                                 arrays: Dict[str, Any]) -> Dict[str, Any]:
    """Multi-host batch assembly: each process contributes ITS OWN rows
    (batch_size/process_count of them) and the result is the global
    dp-sharded batch — per-host batchers legitimately hold different data
    (that IS data parallelism), so a plain device_put of per-host copies
    would silently interleave unrelated rows."""
    from jax.experimental import multihost_utils

    pspecs = plan.registry.batch_specs(tuple(arrays))
    return multihost_utils.host_local_array_to_global_array(
        arrays, plan.mesh, pspecs)
