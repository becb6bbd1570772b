"""Compile the main path's programs for the real chip, without the chip.

The TPU compiler is installed in the sandbox and compiles for a chip
that is described (a v5e 2x2 host) and not attached, so a kernel the
Mosaic compiler refuses, a program XLA:TPU cannot fit, or a sharded step
that does not partition fails HERE and not in a chip call.  Nothing
runs: a compile that passes says nothing about results or times.

The only file that describes a topology.  The description loads the
TPU library, which one process at a time may hold: it happens inside a
fixture (never at import, in a skipif or in a parametrize argument), so
every xdist worker collects the same tests and only the worker handed
this file loads the library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import __graft_entry__ as ge
from _hlo import (score_gathers, wide_dimensions, wide_reduces,
                  wide_row_orderings, wide_scatters)
from textsummarization_on_flink_tpu.config import HParams, resolve_enc_block
from textsummarization_on_flink_tpu.decode import beam_search
from textsummarization_on_flink_tpu.models import get_family
from textsummarization_on_flink_tpu.models import transformer as tfm
from textsummarization_on_flink_tpu.ops import pallas_attention as pa
from textsummarization_on_flink_tpu.ops import topk
from textsummarization_on_flink_tpu.parallel import mesh as mesh_lib
from textsummarization_on_flink_tpu.train import trainer as trainer_lib

SLOTS, CHUNK = 8, 25


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else: logs in /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of these
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """The tree's shapes, placed on the described device."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _params(hps, dev):
    return _on(dev, jax.eval_shape(
        lambda: get_family(hps.model_family).init_params(
            hps, hps.vocab_size, jax.random.PRNGKey(0))))


def _enc_arrays(hps, rows, dev, width=None):
    hps = hps.replace(max_enc_steps=width or hps.max_enc_steps)
    return _on(dev, ge._decode_arrays(hps, np.random.RandomState(0), rows))


def _family_hps(family: str) -> HParams:
    """Reference width; the transformer's depth is cut to two layers a
    side (the slot programs' structure does not depend on it, their
    compile time does)."""
    if family == "transformer":
        return HParams(model_family=family, mode="decode", enc_layers=2,
                       dec_layers=2)
    return HParams(mode="decode")


# -- kernels ---------------------------------------------------------------

def _fused_attention(dev, B, T, D, dtype, blocked):
    f32 = jnp.float32
    shapes = [((B, T, D), dtype), ((B, T, D), dtype), ((B, T), f32),
              ((B, D), f32), ((B, T), f32), ((D,), f32), ((D,), f32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=dev) for s, d in shapes]
    kernel = pa._attention_pallas_blocked if blocked else pa._attention_pallas
    return jax.jit(lambda *a: kernel(*a, True)).lower(*args).compile()


def _flash_block(dev, monkeypatch, B, T, hidden, heads):
    """The transformer's self-attention block with the flash kernel
    forced.  `_use_flash` asks jax for the backend and sees the CPU in
    such a compile: steered here, in the test."""
    monkeypatch.setenv("TS_FLASH", "on")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hps = HParams(model_family="transformer", hidden_dim=hidden,
                  num_heads=heads, max_enc_steps=T, batch_size=B)
    w = jax.ShapeDtypeStruct((hidden, hidden), jnp.float32, sharding=dev)
    p = {k: w for k in ("wq", "wk", "wv", "wo")}
    x = jax.ShapeDtypeStruct((B, T, hidden), jnp.float32, sharding=dev)
    mask = jax.ShapeDtypeStruct((B, T), jnp.float32, sharding=dev)
    return jax.jit(
        lambda p, x, mask: tfm._self_attention(hps, p, x, mask,
                                               causal=False)
    ).lower(p, x, mask).compile()


KERNELS = {
    "fused_attention_simple_f32": lambda dev, mp: _fused_attention(
        dev, 16, 400, 512, jnp.float32, blocked=False),
    "fused_attention_simple_bf16": lambda dev, mp: _fused_attention(
        dev, 16, 400, 512, jnp.bfloat16, blocked=False),
    "fused_attention_blocked": lambda dev, mp: _fused_attention(
        dev, 4, 4096, 512, jnp.float32, blocked=True),
    # T=400/hd=32 is zero-padded to the kernel's T=512/hd=128 grid
    "flash_T400_hd32_padded": lambda dev, mp: _flash_block(
        dev, mp, 16, 400, 256, 8),
    "flash_T2048_hd128": lambda dev, mp: _flash_block(
        dev, mp, 4, 2048, 1024, 8),
}


@pytest.mark.parametrize("case", sorted(KERNELS))
def test_kernel_compiles_for_v5e(case, one_chip, monkeypatch):
    compiled = KERNELS[case](one_chip, monkeypatch)
    assert "tpu_custom_call" in compiled.as_text()


# -- whole programs, pointer-generator at reference width ------------------

def test_pg_train_step_compiles_for_v5e(one_chip):
    hps = HParams()
    state = _on(one_chip, jax.eval_shape(
        lambda: trainer_lib.init_train_state(hps, hps.vocab_size, seed=0)))
    arrays = _on(one_chip, ge._example_arrays(hps, np.random.RandomState(0)))
    compiled = jax.jit(trainer_lib.make_train_step(hps),
                       donate_argnums=0).lower(state, arrays).compile()
    # params + Adagrad state + activations: far inside one chip's 16 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_pg_beam_search_compiles_for_v5e(one_chip):
    hps = _family_hps("pointer_generator")
    beam_search.run_beam_search_jit.lower(
        _params(hps, one_chip), hps,
        _enc_arrays(hps, hps.batch_size, one_chip),
        loop="chunked", chunk=CHUNK).compile()


@pytest.mark.parametrize("bucket", [100, 400])
def test_prefill_compiles_for_v5e(bucket, one_chip):
    hps = _family_hps("pointer_generator")
    beam_search.prefill_jit.lower(
        _params(hps, one_chip), hps,
        _enc_arrays(hps, 1, one_chip, width=bucket)).compile()


def _orders_no_vocabulary_row(compiled, hps):
    """The beam step keeps 2 x beam of the pointer mixture by selection
    over the vocabulary's row and the article's own ids (ops/topk.py):
    XLA:TPU turns a `vmap`ped ``lax.top_k`` into a full sort of every
    [.., 50 128] row, 86% of the slot step's device time before ISSUE
    26, and expands the mixture's scatter-add over that row into half
    of what was left (ISSUE 31) — in the chip's own compiler's output
    no sort and no top-k may have an operand as wide as the vocabulary,
    no scatter a result that wide, and nothing the extended width.  Nor
    is a word's score looked up by id in the step's score block: the
    article's words are scored by a product with the head's columns,
    gathered once before the loop (ISSUE 33; XLA:TPU gathers at 18-19
    ns an index, 102 400 of them a step in the cell).  And the
    selection reads the row once for every ``_plan`` picks of its 2 x
    beam, where the parent read it once a pick (ISSUE 37: each read of
    the cell's [256, 4, 50 000] block is 0.25 ms of a 6.6 ms step)."""
    text = compiled.as_text()
    V, width = hps.vocab_size, hps.vocab_size + hps.max_oov_buckets
    k = 2 * hps.beam_size
    assert 1 <= len(wide_reduces(text, V)) <= -(-k // topk._plan(V, k)) < k
    assert not score_gathers(text, V, SLOTS * hps.beam_size)
    assert wide_dimensions(text, V)  # the step does hold the vocabulary
    assert not wide_row_orderings(text, V)
    assert not wide_row_orderings(text, width)
    assert not wide_scatters(text, V)
    assert not wide_dimensions(text, width)


def _slot_step(hps, slots, dev):
    """The slot step compiled for the described chip, an arena of half
    the pages that ``slots`` full-length articles would take."""
    params = _params(hps, dev)
    arrays = _enc_arrays(hps, slots, dev)
    active = jax.ShapeDtypeStruct((slots,), np.bool_, sharding=dev)
    b_max = -(-hps.max_enc_steps // resolve_enc_block(hps))
    pages = slots * b_max // 2
    state = _on(dev, jax.eval_shape(
        lambda: beam_search.init_slots_jit(params, hps, arrays, pages)))
    table = jax.ShapeDtypeStruct((slots, b_max), np.int32, sharding=dev)
    return beam_search.step_slots_jit.lower(
        params, hps, state, active, table, CHUNK).compile()


@pytest.mark.parametrize("family", ["pointer_generator", "transformer"])
def test_slot_step_compiles_for_v5e(family, one_chip):
    hps = _family_hps(family)
    _orders_no_vocabulary_row(_slot_step(hps, SLOTS, one_chip), hps)


# -- one program across the four chips -------------------------------------

def test_sharded_train_step_compiles_for_v5e_2x2(topo):
    hps = HParams(dp=2, tp=2)
    plan = mesh_lib.make_mesh(hps, devices=topo.devices)
    step = mesh_lib.make_sharded_train_step(plan, donate=False)
    state = jax.eval_shape(
        lambda: trainer_lib.init_train_state(hps, hps.vocab_size, seed=0))
    reg = plan.registry
    state = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state, reg.shardings(reg.state_specs(state)))
    arrays = ge._example_arrays(hps, np.random.RandomState(0))
    arrays = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=s)
              for (k, v), s in zip(
                  arrays.items(),
                  (reg.shardings(reg.batch_specs())[k] for k in arrays))}
    compiled = step.lower(state, arrays).compile()
    text = compiled.as_text()
    assert "all-reduce" in text  # the dp gradient reduction is in there
    # per-device bytes: the tp-sharded [H, V] leaves halve, and the
    # whole step stays far inside one chip's 16 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30
