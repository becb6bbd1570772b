"""The plain references against the program on the CPU at a tiny size:
training loss, gradients, and beam search, on seed-made weights; and the
rule of harness/families/__init__.py, the activations' type stated apart
from the parameters' stored type, for every family module on disk."""
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import correct
from harness import reference as ref
from harness import traffic, weights
from tests.tiny import BENCH, mid_batches, mid_config, tiny_config


def _program_hps(cfg, batch):
    from textsummarization_on_flink_tpu.config import HParams

    return HParams(batch_size=batch, **cfg["hparams"])


def _arrays(cfg, rng, B):
    hp = cfg["hparams"]
    Te, Td, V = hp["max_enc_steps"], hp["max_dec_steps"], hp["vocab_size"]
    a = {"enc_batch": np.full((B, Te), 1, np.int32),
         "enc_batch_extend_vocab": np.full((B, Te), 1, np.int32),
         "enc_lens": np.zeros((B,), np.int32),
         "enc_padding_mask": np.zeros((B, Te), np.float32),
         "dec_batch": np.full((B, Td), 1, np.int32),
         "target_batch": np.full((B, Td), 1, np.int32),
         "dec_padding_mask": np.zeros((B, Td), np.float32)}
    for i in range(B):
        n = rng.randint(3, Te + 1)
        ids = rng.randint(4, V, size=n)
        ext = ids.copy()
        oov = rng.rand(n) < 0.2
        ext[oov] = V + rng.randint(0, 2, size=oov.sum())
        a["enc_batch"][i, :n] = np.where(oov, 0, ids)
        a["enc_batch_extend_vocab"][i, :n] = ext
        a["enc_lens"][i] = n
        a["enc_padding_mask"][i, :n] = 1
        d = rng.randint(2, Td + 1)
        tgt = rng.randint(4, V, size=d)
        tgt[0] = ext[0]  # a copyable target, possibly out of vocabulary
        a["dec_batch"][i, :d] = np.concatenate(
            [[2], np.where(tgt[:-1] >= V, 0, tgt[:-1])])
        a["target_batch"][i, :d] = tgt
        a["dec_padding_mask"][i, :d] = 1
    return a


def fixed_case(hp, n=4):
    """n articles (a few out-of-vocabulary words among them) and an
    output for each, every third token copied from its article and STOP
    last: fixed, at the sizes of `hp`."""
    V, Td = hp["vocab_size"], hp["max_dec_steps"]
    mix = {"article": {"length": {"dist": "lognormal", "median": 40,
                                  "sigma": 0.4, "min": 16,
                                  "max": hp["max_enc_steps"]},
                       "oov_share": 0.02, "oov_pool": 50,
                       "max_oov_buckets": hp["max_oov_buckets"]}}
    arts = traffic.make_articles(mix, V, n, 3)
    rng = np.random.RandomState(3)
    outs = []
    for a in arts:
        out = rng.randint(4, V, size=Td - 4)
        out[::3] = a.ext[:len(out):3][:len(out[::3])]
        outs.append([int(t) for t in out] + [ref.STOP_ID])
    return [(a.ids, a.ext) for a in arts], outs


@pytest.mark.parametrize("name", ["pg_see2017", "tf_cnndm"])
def test_loss_and_gradients_agree(name):
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib

    cfg = tiny_config(name)
    hps = _program_hps(cfg, 4)
    params = weights.make_params(cfg, 5)
    arrays = _arrays(cfg, np.random.RandomState(0), 4)
    grads, (loss, _, _) = trainer_lib.make_grad_fn(hps)(
        params, {k: jnp.asarray(v) for k, v in arrays.items()})
    fam = ref.family(cfg["family"])
    rloss, rgrads = ref.loss_and_grads(
        fam, params, cfg["hparams"],
        {k: jnp.asarray(v) for k, v in arrays.items()}, block=2)
    assert abs(float(loss) - float(rloss)) < 1e-5 * abs(float(rloss))
    for (path, g), r in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_leaves(rgrads)):
        scale = max(float(jnp.max(jnp.abs(r))), 1e-6)
        assert float(jnp.max(jnp.abs(g - r))) < 2e-4 * scale + 1e-7, path


@pytest.mark.parametrize("name", ["pg_see2017", "tf_cnndm"])
def test_beam_search_and_scores_agree(name):
    from textsummarization_on_flink_tpu.decode import beam_search

    cfg = tiny_config(name)
    cfg["init"]["stop_bias"] = 1.0
    hps = _program_hps(cfg, 4)
    params = weights.make_params(cfg, 7)
    arrays = _arrays(cfg, np.random.RandomState(1), 4)
    out = jax.device_get(beam_search.run_beam_search_jit(
        params, hps, {k: v for k, v in arrays.items()
                      if k.startswith("enc_")}, loop="scan"))
    fam = ref.family(cfg["family"])
    arts, outs = [], []
    for b in range(4):
        n = int(arrays["enc_lens"][b])
        art = (arrays["enc_batch"][b, :n],
               arrays["enc_batch_extend_vocab"][b, :n])
        toks, avg = ref.beam_search(fam, params, cfg["hparams"], *art)
        served = [int(t) for t in out.tokens[b][1:int(out.length[b])]]
        assert abs(avg - float(out.avg_log_prob[b])) < 1e-4 * abs(avg), b
        assert toks == served, b
        arts.append(art)
        outs.append(served)
    scores = ref.score_tokens(fam, params, cfg["hparams"], arts, outs)
    for b in range(4):
        assert abs(scores[b] / int(out.length[b])
                   - float(out.avg_log_prob[b])) < 1e-4


# ------------------------------------------- the activations' type (PR 36)

FAMILIES = sorted(os.path.basename(f)[:-3] for f in glob.glob(
    os.path.join(BENCH, "harness", "families", "[!_]*.py")))
with open(os.path.join(os.path.dirname(__file__),
                       "parent_reference.json")) as f:
    RECORDED = json.load(f)["families"]


def _mid_config_of(family, param_dtype):
    """The first configuration on disk of `family`, at the middle size,
    storing its parameters in `param_dtype`."""
    for path in sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))):
        with open(path) as f:
            if json.load(f)["family"] == family:
                cfg = mid_config(os.path.basename(path)[:-5])
                cfg["param_dtype"] = param_dtype
                return cfg
    raise AssertionError(f"no configuration of family {family!r} on disk")


def _scores(cfg, params, act, case):
    return ref.score_tokens(ref.family(cfg["family"]), params,
                            cfg["hparams"], *case, act=act)


def _close(got, want, rel=1e-6):
    return np.asarray(got, np.float64) == pytest.approx(
        np.asarray(want, np.float64), rel=rel)


@pytest.mark.parametrize("family", sorted(RECORDED))
def test_a_float32_tree_scores_as_recorded(family):
    """(a) Over a float32 tree the cast where a leaf is read is nothing
    (sound) or reads leaves already rounded (control), so `score_tokens`
    reads what the parent of PR 36 read (bit for bit on the builder's
    machine; another XLA:CPU may reorder a reduction), the transformer's
    repaired control apart: parent_reference.json says which."""
    assert family in FAMILIES
    want = RECORDED[family]["score_tokens"]
    cfg = _mid_config_of(family, "float32")
    assert cfg["name"] == RECORDED[family]["config"]
    params = weights.make_params(cfg, 3)
    case = fixed_case(cfg["hparams"])
    assert _close(_scores(cfg, params, ref.SOUND, case), want["sound"])
    assert _close(_scores(cfg, correct.low_precision(params), ref.CONTROL,
                          case), want["control"])


@pytest.mark.parametrize("family", sorted(RECORDED))
def test_a_float32_tree_trains_as_recorded(family):
    """(a) for `train_numbers`: the sound reference's three steps and
    the control's gaps from them.  A gap is a difference of norms that
    agree to 1e-3, so it is held to 1e-4 of itself."""
    want = RECORDED[family]
    cfg = _mid_config_of(family, "float32")
    hp = cfg["hparams"]

    class Run:
        batches = mid_batches(hp, rows=4)

    losses, g1, d3 = ref.train_steps(
        ref.family(family), weights.make_params(cfg, 3), hp, Run.batches, 2)
    sound = want["train_steps"]
    assert _close(losses, sound["losses"])
    assert _close(g1, sound["first_gradient_norms"])
    assert _close(d3, sound["change_norms"])
    control = correct.train_numbers(cfg, 3, Run, block=2, control=True)
    assert _close(control["_detail"]["ref_losses"], sound["losses"])
    assert _close(control["_detail"]["losses"],
                  want["train_control"]["losses"])
    for k in ("loss_gap", "grad_norm_gap", "update_norm_gap"):
        assert control[k] == pytest.approx(want["train_control"][k],
                                           rel=1e-4, abs=1e-7), k


@pytest.mark.parametrize("family", FAMILIES)
def test_a_bfloat16_tree_has_a_float32_reference_and_a_control(family):
    """(b) A configuration that stores bfloat16 parameters: its control
    (the leaves as stored, bfloat16 activations) parts from its sound
    reference (the leaves widened where read, everything else float32) by
    more than 1e-3 a token on the worst of eight articles, and the sound
    reference is the float32 computation over the same bfloat16 VALUES
    held in a float32 tree."""
    cfg = _mid_config_of(family, "bfloat16")
    params = weights.make_params(cfg, 3)
    assert {x.dtype for x in jax.tree_util.tree_leaves(params)} == {
        jnp.dtype(jnp.bfloat16)}
    case = fixed_case(cfg["hparams"], 8)
    sound = _scores(cfg, params, ref.SOUND, case)
    control = _scores(cfg, correct.low_precision(params), ref.CONTROL, case)
    tokens = np.asarray([len(out) + 1 for out in case[1]])
    assert np.max(np.abs(sound - control) / tokens) > 1e-3
    widened = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    assert _close(sound, _scores(cfg, widened, ref.SOUND, case))


@pytest.mark.parametrize("family", FAMILIES)
def test_the_sound_reference_takes_a_bfloat16_tree_as_it_is_stored(family):
    """(c) No float32 copy of the tree is made outside the program: the
    jitted sound reference's arguments are the tree's own bytes and the
    inputs', within 1%."""
    cfg = _mid_config_of(family, "bfloat16")
    hp = cfg["hparams"]
    params = weights.make_params(cfg, 3)
    inputs = ref.score_inputs(hp, *fixed_case(hp))
    compiled = ref.score_program(ref.family(family), hp, ref.SOUND).lower(
        params, *inputs).compile()
    stored = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    assert stored == 2 * weights.n_params(
        ref.family(family).param_specs(hp))
    want = stored + sum(x.nbytes for x in inputs)
    got = compiled.memory_analysis().argument_size_in_bytes
    assert abs(got - want) <= 0.01 * want, (got, want)
