"""GSPMD sharding-spec registry: ONE mesh story for train, serve, and
checkpoint (ISSUE 8; PERF.md "One mesh").

Every tensor role in the system maps HERE — and only here — to a
`PartitionSpec` over the named ``(dp, tp, sp)`` mesh, plus (where a role
is reduced across the mesh) a *wire dtype* annotation:

  role            spec source                  wire dtype
  --------------  ---------------------------  -----------------------
  params          `param_spec` (per-leaf rule) —
  opt_state       same tree rule as params     —
  step counter    replicated                   —
  train batch     `batch_spec` (dp rows,       —
                  sp over T_enc)
  eval batch      same as train batch          —
  step metrics    replicated scalars           —
  grads           same tree rule as params     ``hps.grad_allreduce_dtype``
  beam output     dp over articles             —
  slot state      dp over resident slots       —
  prefill batch   dp over prefill rows at      —
                  bucket shapes (replicated
                  for 1-article serving
                  prefills)
  prefill state   same leading-axis rule       —

Consumers: the unified train/eval step builders (parallel/mesh.py), the
serving paths (`make_sharded_beam_search`, `decode/decoder.py`'s
`SlotDecodeEngine`), the checkpointer (`Checkpointer.restore_sharded`),
and bench/roofline byte accounting (`analytic_comms`).  No step builder
constructs its own PartitionSpecs — layout decisions live in this one
declarative place so batch/mesh size can grow to fill the hardware
without touching application code (the FastSeq restructuring applied to
the whole system; SNIPPETS.md [2]/[3]).

The wire-dtype annotation is how the bf16 gradient all-reduce lever
(PR 5's 86 -> 43 MB/step) rides ANY dp x tp mesh: the registry says
*what* is reduced over dp and *in what dtype*; the step builder groups
the batch ``[B] -> [dp, B/dp]``, computes per-group grads under `vmap`,
casts the stacked grads to the wire dtype under a sharding constraint
``P("dp", *param_spec)``, and sums over the group axis — XLA's
partitioner turns that sum into the dp all-reduce at the wire dtype.
The constraint+sum route keeps the whole step ONE pjit program: no
manual collective, so forward-internal tp collectives stay wherever
GSPMD puts them.

Note on CPU HLO: the CPU backend promotes sub-f32 all-reduces to f32
around a convert pair, so a faked-mesh compile shows an f32 wire with
bf16 *rounding semantics* (parity tests pin those); on TPU the wire is
genuinely bf16.  The comms gate therefore pins the reduced ELEMENT
count from HLO and prices bytes at the registry's wire dtype.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from textsummarization_on_flink_tpu.config import HParams

PyTree = Any

#: Canonical mesh axis order (parallel/mesh.py builds meshes in this
#: order; replica-group attribution in the comms gate depends on it).
MESH_AXES = ("dp", "tp", "sp")

#: The train/eval batch array names (the model-family input contract).
BATCH_NAMES = ("enc_batch", "enc_lens", "enc_padding_mask",
               "enc_batch_extend_vocab", "dec_batch", "target_batch",
               "dec_padding_mask")

#: Encoder-side names only (the beam-search / serving input contract).
ENC_BATCH_NAMES = ("enc_batch", "enc_lens", "enc_padding_mask",
                   "enc_batch_extend_vocab")

#: Every role the registry answers for (`ShardingRegistry.table()`
#: documents each; tests assert coverage).
ROLES = ("params", "opt_state", "step", "train_batch", "eval_batch",
         "metrics", "grads", "beam_output", "slot_state",
         "prefill_batch", "prefill_state", "arena_pool", "page_table")


# --------------------------------------------------------------------------
# Spec rules (pure: hps + tensor role -> PartitionSpec)
# --------------------------------------------------------------------------

def param_spec(path: Tuple[Any, ...], leaf: Any = None) -> P:
    """PartitionSpec for one model-family parameter leaf.

    Pointer-generator: vocab-dimension tensors shard over `tp`;
    everything else (LSTM kernels, attention, reduce — all small:
    ~[384,1024] at the default config) is replicated, which keeps their
    per-step all-reduce traffic at zero.

    Transformer: the tied [V, H] embedding and [V] out_bias shard over
    vocab; attention wq/wk/wv and ffn w1 column-shard (heads/ffn over
    tp), wo and ffn w2 row-shard — the Megatron layout, so each
    attention/FFN block needs exactly one all-reduce on its output.
    """
    keys = tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)
    if "embedding" in keys:
        return P("tp", None)  # [V, E|H] row-sharded over vocab
    if "output_projection" in keys:
        if keys[-1] == "w":
            return P(None, "tp")  # [H, V] column-sharded over vocab
        return P("tp")  # bias v: [V]
    if keys[-1] == "out_bias":
        return P("tp")  # transformer tied-projection bias [V]
    if keys[-1] in ("wq", "wk", "wv", "w1"):
        return P(None, "tp")  # heads / ffn hidden over tp
    if keys[-1] in ("wo", "w2"):
        return P("tp", None)  # row-parallel back to H
    if keys[-1] == "b1":
        return P("tp")  # ffn hidden bias [F]
    return P()


def param_specs(params: PyTree) -> PyTree:
    """PartitionSpec tree for a parameter pytree (grads and Adagrad
    accumulators share this tree rule — same structure, same layout)."""
    return jax.tree_util.tree_map_with_path(param_spec, params)


def batch_spec(name: str) -> P:
    """Batch arrays shard over dp on axis 0; encoder-sequence-major
    arrays additionally shard T_enc over sp (context parallelism)."""
    if name in ("enc_batch", "enc_padding_mask", "enc_batch_extend_vocab"):
        return P("dp", "sp")
    return P("dp")


def state_specs(state: Any) -> Any:
    """Specs for a full TrainState: params and the Adagrad accumulators
    share the param tree rule; the scalar step is replicated."""
    pspecs = param_specs(state.params)
    acc_specs = param_specs(state.opt_state.accumulators)
    return type(state)(
        params=pspecs,
        opt_state=type(state.opt_state)(accumulators=acc_specs),
        step=P(),
    )


def grouped_batch_spec(name: str) -> P:
    """Spec for a batch array regrouped ``[B, ...] -> [dp, B/dp, ...]``
    (the wire-dtype gradient path): the group axis carries dp, the row
    axis un-shards, trailing axes keep their batch rule."""
    return P("dp", None, *batch_spec(name)[1:])


def stacked_grad_spec(leaf_spec: P) -> P:
    """Spec for per-dp-group grads stacked on a leading axis: dp leads,
    the leaf keeps its param-rule layout — constraining the stacked
    tree to this in the wire dtype is what makes XLA lower the group
    sum to the dp all-reduce at that dtype."""
    return P("dp", *leaf_spec)


def wire_dtype(hps: HParams, role: str = "grads"):
    """The dtype a reduced role rides the mesh wire in, or None when the
    reduction stays in the tensor's own dtype (XLA's default psum)."""
    if role == "grads" \
            and getattr(hps, "grad_allreduce_dtype", "float32") == "bfloat16":
        import jax.numpy as jnp

        return jnp.bfloat16
    return None


# --------------------------------------------------------------------------
# Registry (mesh-bound: specs + NamedSharding materialization)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardingRegistry:
    """The mesh-bound registry: every consumer asks THIS object for
    specs/shardings; nothing else constructs PartitionSpecs."""

    mesh: Mesh
    hps: HParams

    # -- axis sizes --
    @property
    def dp(self) -> int:
        return self.mesh.shape["dp"]

    @property
    def tp(self) -> int:
        return self.mesh.shape["tp"]

    @property
    def sp(self) -> int:
        return self.mesh.shape["sp"]

    # -- spec trees per role --
    def param_specs(self, params: PyTree) -> PyTree:
        return param_specs(params)

    def state_specs(self, state: Any) -> Any:
        return state_specs(state)

    def batch_spec(self, name: str) -> P:
        return batch_spec(name)

    def grouped_batch_spec(self, name: str) -> P:
        return grouped_batch_spec(name)

    def stacked_grad_spec(self, leaf_spec: P) -> P:
        return stacked_grad_spec(leaf_spec)

    def batch_specs(self, names: Sequence[str] = BATCH_NAMES,
                    ) -> Dict[str, P]:
        return {k: batch_spec(k) for k in names}

    def metric_specs(self) -> Any:
        """Replicated scalars, as a StepMetrics tree."""
        from textsummarization_on_flink_tpu.train import trainer as trainer_lib

        return trainer_lib.StepMetrics(
            loss=P(), coverage_loss=P(), total_loss=P(), global_norm=P())

    def beam_output_specs(self) -> Any:
        """Serving decode output: articles shard over dp, beams stay
        chip-local (zero cross-chip traffic in the decode loop)."""
        from textsummarization_on_flink_tpu.decode import beam_search

        return beam_search.BeamSearchOutput(
            tokens=P("dp"), length=P("dp"), avg_log_prob=P("dp"),
            attn_dists=P("dp"), p_gens=P("dp"))

    def slot_state_specs(self, state: PyTree) -> PyTree:
        """Continuous-serving SlotState, in two placement classes
        (ISSUE 20).  Slot-leading leaves (beam, enc_rest,
        masks/lengths) lead with the [slots, ...] axis, sharded over dp
        (slots % dp == 0, validated by the engine); per-slot beams stay
        chip-local like the batch search.  The page POOLS and the
        scratch row lead with the [pages+1, ...] arena axis, which has
        no relation to dp — they replicate (role ``arena_pool``), and
        the page TABLE passed alongside as data replicates too (role
        ``page_table``); every chip addresses its slots' pages locally.
        Sharding the arena itself over dp (per-chip sub-arenas with a
        dp-local free list) is a deferred follow-on — it needs the host
        allocator split per chip, not just a spec change here.
        """
        from textsummarization_on_flink_tpu.decode import beam_search

        dp = jax.tree_util.tree_map(lambda _: P("dp"), state)
        rep = jax.tree_util.tree_map(lambda _: self.arena_pool_spec(),
                                     state)
        return beam_search.SlotState(
            beam=dp.beam, enc_rest=dp.enc_rest,
            enc_pages=rep.enc_pages, ext_pool=rep.ext_pool,
            attn_pool=rep.attn_pool, enc_mask=dp.enc_mask,
            enc_valid_len=dp.enc_valid_len)

    def arena_pool_spec(self) -> P:
        """Page pools ([pages+1, block, ...] leaves of a SlotState):
        replicated — the arena axis is allocator
        bookkeeping, not a device axis (see slot_state_specs)."""
        return P()

    def page_table_spec(self) -> P:
        """The per-slot page table ([slots, B_max] int32, traced DATA
        never shape): replicated, like the length/mask operands of the
        compile-once kernels — it is tiny and consulted by every chip's
        gather."""
        return P()

    def slot_batch_specs(self) -> Dict[str, P]:
        """Encoder arrays stacked over slots (the slot-init contract):
        the slots axis shards over dp; T_enc stays unsharded (continuous
        serving pads to ONE resident shape, no sp context parallelism
        in the slot loop)."""
        return {k: P("dp") for k in ENC_BATCH_NAMES}

    # -- prefill/decode disaggregation (ISSUE 11) --
    def prefill_batch_spec(self, rows: int = 1) -> P:
        """PREFILL-stage placement rule: bucket-shaped encoder arrays
        batch-shard over dp when the prefill batch divides the axis;
        the continuous engine's one-article prefill replicates (its
        [1, bucket] leaves cannot split, and dp's job in serving is
        sharding the RESIDENT slots — the two stages place separately
        from this one table)."""
        return P("dp") if rows >= self.dp and rows % self.dp == 0 else P()

    def prefill_batch_specs(self, rows: int = 1) -> Dict[str, P]:
        spec = self.prefill_batch_spec(rows)
        return {k: spec for k in ENC_BATCH_NAMES}

    def prefill_state_specs(self, pre: PyTree) -> PyTree:
        """Specs for a PrefillState (padded encoder view + valid
        length, leading axis = the prefill batch): same leading-axis
        rule as the input arrays, so a prefilled article lands where
        pack_slot_jit's scatter into the dp-sharded resident state
        expects it."""
        rows = jax.tree_util.tree_leaves(pre)[0].shape[0]
        spec = self.prefill_batch_spec(rows)
        return jax.tree_util.tree_map(lambda _: spec, pre)

    def wire_dtype(self, role: str = "grads"):
        return wire_dtype(self.hps, role)

    # -- NamedSharding materialization / placement --
    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def shardings(self, spec_tree: PyTree) -> PyTree:
        return jax.tree_util.tree_map(
            self.named, spec_tree, is_leaf=lambda x: isinstance(x, P))

    def constrain(self, x: Any, spec: P) -> Any:
        """with_sharding_constraint against this registry's mesh — the
        one sanctioned way for traced code to pin a layout."""
        return jax.lax.with_sharding_constraint(x, self.named(spec))

    def shard_state(self, state: Any) -> Any:
        """Place a host-resident TrainState onto the mesh."""
        specs = self.state_specs(state)
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, self.named(s)), state, specs,
            is_leaf=lambda x: isinstance(x, P))

    def shard_batch(self, arrays: Dict[str, Any]) -> Dict[str, Any]:
        return {k: jax.device_put(v, self.named(batch_spec(k)))
                for k, v in arrays.items()}

    def shard_params(self, params: PyTree) -> PyTree:
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, self.named(s)), params,
            param_specs(params), is_leaf=lambda x: isinstance(x, P))

    # -- documentation --
    def table(self) -> List[Dict[str, str]]:
        """The role -> spec -> wire-dtype table (PERF.md "One mesh";
        tests assert it covers ROLES)."""
        w = self.hps.grad_allreduce_dtype
        rows = [
            {"role": "params", "spec": "per-leaf rule (vocab/heads over "
                                       "tp, else replicated)", "wire": "-"},
            {"role": "opt_state", "spec": "same tree rule as params",
             "wire": "-"},
            {"role": "step", "spec": "P()", "wire": "-"},
            {"role": "train_batch", "spec": "P('dp'[, 'sp'])", "wire": "-"},
            {"role": "eval_batch", "spec": "P('dp'[, 'sp'])", "wire": "-"},
            {"role": "metrics", "spec": "P()", "wire": "-"},
            {"role": "grads", "spec": "same tree rule as params",
             "wire": w},
            {"role": "beam_output", "spec": "P('dp')", "wire": "-"},
            {"role": "slot_state", "spec": "P('dp')", "wire": "-"},
            {"role": "prefill_batch",
             "spec": "P('dp') at bucket shapes when the prefill batch "
                     "divides dp, else P()", "wire": "-"},
            {"role": "prefill_state", "spec": "same leading-axis rule "
                                              "as prefill_batch",
             "wire": "-"},
            {"role": "arena_pool",
             "spec": "P() — [pages+1, block, ...] pools replicate; the "
                     "arena axis is allocator bookkeeping, not a device "
                     "axis", "wire": "-"},
            {"role": "page_table",
             "spec": "P() — [slots, B_max] int32 traced data, "
                     "replicated like length/mask operands", "wire": "-"},
        ]
        return rows


@functools.lru_cache(maxsize=16)
def _registry_cached(mesh: Mesh, hps: HParams) -> ShardingRegistry:
    return ShardingRegistry(mesh=mesh, hps=hps)


def registry_for(plan: Any) -> ShardingRegistry:
    """The registry for a parallel/mesh.MeshPlan (cached: one registry
    per (mesh, hps) pair, so every consumer sees the same object)."""
    return _registry_cached(plan.mesh, plan.hps)


# --------------------------------------------------------------------------
# Analytic comms accounting (the CPU-verifiable wire-byte claims)
# --------------------------------------------------------------------------

def analytic_comms(hps: HParams, params: Optional[PyTree] = None) -> dict:
    """Per-step collective-byte prediction from the registry specs alone
    (no mesh, no compile — importable wherever HParams is).

    Returns::

      param_elements     total parameter scalars
      dp_grad_elements   per-device elements the dp gradient all-reduce
                         moves each step: tp-sharded leaves contribute
                         their SHARD (each tp group reduces its own
                         slice over dp); replicated leaves contribute
                         their full size (every tp replica reduces its
                         own copy)
      dp_wire_bytes      dp_grad_elements x wire-dtype size — 43.0 MB
                         at reference scale under the bf16 wire, the
                         retired lowp path's committed number
      wire_dtype         the registry's grad wire dtype name
      tp_scores_bytes    analytic ceiling anchor for the tp activation
                         collectives: the per-step [T_dec, B, V]
                         scores-shaped all-gather/reduce at compute
                         dtype (0 when tp == 1)

    The comms gate (tests/test_bytes_gate.py) pins the HLO-measured
    element counts against dp_grad_elements and prices bytes at the
    wire dtype, because the CPU backend promotes bf16 all-reduces to
    f32 around a convert pair (see module docstring).
    """
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib

    if params is None:
        params = jax.eval_shape(
            lambda: trainer_lib.init_train_state(
                hps, hps.vocab_size, seed=0)).params
    tp = max(int(hps.tp), 1)
    total = 0
    dp_elems = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        elems = int(np.prod(leaf.shape)) if leaf.shape else 1
        total += elems
        spec = param_spec(path, leaf)
        dp_elems += elems // tp if "tp" in spec else elems
    wire = hps.grad_allreduce_dtype
    wire_size = 2 if wire == "bfloat16" else 4
    compute_size = 2 if hps.compute_dtype == "bfloat16" else 4
    scores = (hps.max_dec_steps * hps.batch_size * hps.extended_vsize
              * compute_size if tp > 1 else 0)
    return {
        "param_elements": total,
        "dp_grad_elements": dp_elems,
        "dp_wire_bytes": dp_elems * wire_size,
        "wire_dtype": wire,
        "tp_scores_bytes": scores,
    }
