"""The decode programs carry their named scopes (OBSERVABILITY.md
"Scopes"): a device trace is split by them, so a refactor that drops one
fails here and not in a trace nobody took.  Checked on the CPU in the
lowered programs' name stacks — what the compiler turns into each
instruction's ``op_name`` — for the slot step, prefill
and the one-step decode of each model family, and in the COMPILED text of
the public accessor the benchmark reads the slot step through, which
returns the executable the engine runs with no second compile.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _slots import Slots

from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.vocab import Vocab
from textsummarization_on_flink_tpu.decode import beam_search
from textsummarization_on_flink_tpu.models import get_family
from textsummarization_on_flink_tpu.obs import Registry
from textsummarization_on_flink_tpu.obs import profile as profile_lib

PG_HPS = HParams(batch_size=2, hidden_dim=8, emb_dim=6, vocab_size=24,
                 max_enc_steps=12, max_dec_steps=8, beam_size=3,
                 min_dec_steps=2, max_oov_buckets=4, mode="decode",
                 decode_enc_block=4)
TF_HPS = PG_HPS.replace(model_family="transformer", hidden_dim=8, emb_dim=8,
                        num_heads=2, enc_layers=2, dec_layers=2)
AAN_HPS = TF_HPS.replace(model_family="avg_attention")

#: scopes of the model step, per family (no LSTM cell in a transformer)
STEP = {"pointer_generator": {"attention", "lstm_cell", "vocab_dist",
                              "topk"},
        "transformer": {"attention", "vocab_dist", "topk"},
        "avg_attention": {"attention", "vocab_dist", "topk"}}
FAMILIES = [pytest.param(h, id=h.model_family)
            for h in (PG_HPS, TF_HPS, AAN_HPS)]


@pytest.fixture(scope="module", autouse=True)
def _metadata_in_the_cache_key():
    """JAX's persistent compile cache leaves metadata out of its key by
    default: a hit hands back the executable with the op_names of
    whichever build compiled it.  These tests read op_names, so while
    they run a program whose metadata differs is another cache entry."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    yield
    jax.config.update(flag, before)


def scopes_of(stage) -> set:
    """Every part of every name stack of a lowered program (its debug
    locations) or of every op_name of a compiled one.  A transform wraps
    the scopes under it (`vmap(topk)/top_k`), so a path splits on
    brackets as well as on `/`.  Lowered where possible: it is this
    build's by construction, while a compiled program can come out of a
    compile cache with the metadata of the build that put it there."""
    if hasattr(stage, "compile"):
        names = re.findall(r'loc\("([^"]*)"', stage.as_text(debug_info=True))
    else:
        names = re.findall(r'op_name="([^"]*)"', stage.as_text())
    parts = set()
    for name in names:
        parts.update(re.split(r"[/()]+", name))
    return parts


def _setup(hps):
    family = get_family(hps.model_family)
    params = family.init_params(hps, hps.vocab_size, jax.random.PRNGKey(0))
    B, T = hps.batch_size, hps.max_enc_steps
    rng = np.random.default_rng(1)
    arrays = {
        "enc_batch": rng.integers(4, hps.vocab_size, (B, T)).astype(np.int32),
        "enc_lens": np.full((B,), T, np.int32),
        "enc_padding_mask": np.ones((B, T), np.float32),
        "enc_batch_extend_vocab": rng.integers(
            4, hps.vocab_size, (B, T)).astype(np.int32),
    }
    return family, params, arrays


@pytest.mark.parametrize("hps", FAMILIES)
def test_one_step_decode_carries_the_model_scopes(hps):
    family, params, arrays = _setup(hps)
    enc_one = jax.tree_util.tree_map(
        lambda x: x[0], family.beam_encode(params, hps, arrays))
    init_state, step = family.beam_adapter(hps)
    state = init_state(params, enc_one)
    mask = jnp.asarray(arrays["enc_padding_mask"][0])
    ext = jnp.asarray(arrays["enc_batch_extend_vocab"][0])
    latest = jnp.zeros((hps.beam_size,), jnp.int32)
    lowered = jax.jit(lambda t, tok, st: step(
        params, enc_one, mask, ext, t, tok, st)).lower(
            jnp.int32(0), latest, state)
    assert STEP[hps.model_family] <= scopes_of(lowered)


def test_batched_pg_onestep_carries_the_model_scopes():
    """models/pointer_generator.decode_onestep (the [B, ...] form the
    reference-parity paths call) is scoped like the shared one."""
    from textsummarization_on_flink_tpu.models import pointer_generator as pg

    hps = PG_HPS
    _, params, arrays = _setup(hps)
    enc = pg.run_encoder(params, hps, arrays)
    B, H = hps.batch_size, hps.hidden_dim
    lowered = jax.jit(lambda tok, c, h, cov: pg.decode_onestep(
        params, hps, enc, arrays["enc_padding_mask"],
        arrays["enc_batch_extend_vocab"], tok, (c, h), cov)).lower(
            jnp.zeros((B,), jnp.int32), jnp.zeros((B, H)),
            jnp.zeros((B, H)), jnp.zeros((B, hps.max_enc_steps)))
    assert STEP["pointer_generator"] <= scopes_of(lowered)


@pytest.mark.parametrize("hps", FAMILIES)
def test_prefill_carries_the_encoder_scope(hps):
    _, params, arrays = _setup(hps)
    one = {k: v[:1] for k, v in arrays.items()}
    assert "encoder" in scopes_of(
        beam_search.prefill_jit.lower(params, hps, one))


@pytest.mark.parametrize("hps", FAMILIES)
def test_slot_steps_carry_every_scope(hps):
    _, params, arrays = _setup(hps)
    eng = Slots(params, hps, arrays, hps.batch_size)
    assert STEP[hps.model_family] | {"beam_select", "page_io"} <= scopes_of(
        beam_search.step_slots_jit.lower(
            params, hps, eng.state, np.ones(eng.slots, bool), eng.table, 2))


def test_compiled_slot_step_is_the_engines_own_executable(tmp_path):
    """ServingServer.compiled_slot_step() / SlotDecodeEngine
    .compiled_step(): the program at the engine's shapes, scopes in its
    text, a memory analysis — and neither a new jit-cache entry nor a
    compile-ledger event (it is read after a measured window, where a
    compile would be a fault)."""
    from textsummarization_on_flink_tpu.serve.server import ServingServer
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib

    vocab = Vocab(words=["the", "cat", "sat", "dog", "ran", "."])
    hps = HParams(mode="decode", batch_size=2, hidden_dim=8, emb_dim=6,
                  vocab_size=vocab.size(), max_enc_steps=16,
                  max_dec_steps=6, beam_size=2, min_dec_steps=1,
                  max_oov_buckets=4, serve_buckets="16",
                  serve_mode="continuous", serve_slots=2,
                  serve_refill_chunk=2, serve_arena_pages=8,
                  decode_enc_block=4)
    params = trainer_lib.init_train_state(hps, vocab.size(), seed=0).params
    reg = Registry()
    server = ServingServer(hps, vocab, params=params,
                           decode_root=str(tmp_path / "d"), registry=reg)
    with pytest.raises(RuntimeError):
        server.compiled_slot_step()  # nothing packed yet: no shapes
    with server:
        server.submit("the cat sat .", uuid="a").result(timeout=300)
        engine = server._cont.engine
        sizes = engine.cache_sizes()
        ledger = profile_lib.profiler_for(reg).compile_stats()
        compiled = server.compiled_slot_step()
        assert engine.cache_sizes() == sizes
        assert profile_lib.profiler_for(reg).compile_stats() == ledger
        # and the engine still serves through the same executable
        server.submit("the dog ran .", uuid="b").result(timeout=300)
        assert engine.cache_sizes() == sizes
    assert STEP["pointer_generator"] | {"beam_select", "page_io"} <= \
        scopes_of(compiled)
    assert compiled.memory_analysis() is not None
    # a micro-batch server has no slot step to hand out
    mb = ServingServer(hps.replace(serve_mode="microbatch"), vocab,
                       params=params, decode_root=str(tmp_path / "m"),
                       registry=Registry())
    with pytest.raises(RuntimeError):
        mb.compiled_slot_step()
