"""The family modules (harness/families/): the move out of weights.py,
counts.py and reference_*.py changed no bit and no count (digests and
numbers recorded from the parent commit, PR 26), a name nobody has is an
error that says so, and what a configuration states of its own (parameter
type, rehearsal sizes) and what a family offers of its own (its
distributions) are taken."""
import hashlib
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from harness import counts, readers, reference, traffic, weights
from tests import tiny
from tests.tiny import BENCH, tiny_config


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(x)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


# recorded at the parent (PR 26) with this same function, on the CPU
PARENT_TREES = {
    ("pg_see2017", 5):
        "53bf891d9c178717ca2f54195b1778923490d52ee770cc8a0bfbab481a97a3ff",
    ("pg_see2017", 2 ** 31 + 7):
        "b8dc49b2fc4846c91323c8845e786c2bc44947b51096b75bc74769608412958f",
    ("tf_cnndm", 5):
        "3893d9cd896e43cc0109828bf984892e197f67f1f42f1e1159e33d2b892e9d48",
    ("tf_cnndm", 2 ** 31 + 7):
        "279da646453dcfad5b0fb944c51d5ff12f15cef1ccc8576fb981fea9b1dea4f9",
}
PARENT_COUNTS = {
    "pg_see2017": {
        "train_step": {"flops": 704316112896.0, "bytes": 516414360.0},
        "slot_chunk": {"flops": 126593926400.0, "bytes": 3547412900.0},
        "prefill": {"flops": 653787136.0, "bytes": 6837760.0},
        "n_params": 21501265},
    "tf_cnndm": {
        "train_step": {"flops": 4131913728000.0, "bytes": 1327840152.0},
        "slot_chunk": {"flops": 361274931200.0, "bytes": 25363233700.0},
        "prefill": {"flops": 9940568064.0, "bytes": 64461824.0},
        "n_params": 55310673},
}


@pytest.mark.parametrize("name,mix", [("pg_see2017", "news_open_loop"),
                                      ("tf_cnndm", "cnndm_b64")])
def test_seed_made_weights_are_the_parents_bit_for_bit(name, mix):
    cfg = _config(name)
    with open(os.path.join(BENCH, "traffic", mix + ".json")) as f:
        bench_run.apply_rehearsal(cfg, json.load(f), {"check": {}})
    for seed in (5, 2 ** 31 + 7):
        tree = weights.make_params(cfg, seed)
        assert _digest(tree) == PARENT_TREES[(name, seed)], seed
        assert {x.dtype for x in jax.tree_util.tree_leaves(tree)} == {
            jnp.dtype("float32")}


@pytest.mark.parametrize("name", sorted(PARENT_COUNTS))
def test_counts_are_the_parents_to_the_last_digit(name):
    cfg = _config(name)
    fam, hp = reference.family(cfg["family"]), cfg["hparams"]
    assert counts.train_step(fam, hp, cfg["deployment"]["train"]) == \
        PARENT_COUNTS[name]["train_step"]
    assert counts.slot_chunk(fam, hp, dict(chunk=25, slots=256), 43.25,
                             311.5) == PARENT_COUNTS[name]["slot_chunk"]
    assert counts.prefill(fam, hp, {}, 311.5) == \
        PARENT_COUNTS[name]["prefill"]
    assert counts.n_params(fam, hp) == PARENT_COUNTS[name]["n_params"]
    # a parameter takes the bytes of the configuration's parameter type
    half = counts.slot_chunk(fam, hp, dict(chunk=25, slots=256,
                                           param_bytes=2), 0.0, 311.5)
    assert half["bytes"] == 25 * 2 * PARENT_COUNTS[name]["n_params"]


def _unknown_family():
    reference.family("no_such_family")


def _unknown_count():
    readers.read({"source": {"kind": "roofline", "count": "no_such_count",
                             "pattern": "^jit_train_step$"}},
                 {"trace": {"devices": 1, "programs": {"jit_train_step": {
                     "calls": 1, "total_s": 1.0, "mean_ms": 1e3}}},
                  "family": "transformer", "hparams": {}, "deployment": {},
                  "peaks": {"flops_per_s": 1.0, "bytes_per_s": 1.0}})


def _clock_not_offered():
    cfg = tiny_config("tf_cnndm")
    cfg["init"]["summary_clock"] = {"units": 2}
    weights.make_params(cfg, 1)


@pytest.mark.parametrize("fault,named", [
    (_unknown_family, "no_such_family.*pointer_generator.*transformer"),
    (_unknown_count, "no_such_count"),
    (_clock_not_offered, "summary_clock.*transformer.*wire")])
def test_a_name_nobody_has_is_an_error_that_names_it(fault, named):
    with pytest.raises(ValueError, match=named):
        fault()


def test_a_configuration_states_its_parameter_type():
    cfg = tiny_config("pg_see2017")
    f32 = weights.make_params(cfg, 9)
    cfg["param_dtype"] = "bfloat16"
    assert weights.param_dtype(cfg).itemsize == 2
    b16 = weights.make_params(cfg, 9)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(f32)[0],
                            jax.tree_util.tree_leaves(b16)):
        # drawn (and wired) in float32, rounded once
        assert b.dtype == jnp.bfloat16, path
        assert np.array_equal(np.asarray(a.astype(jnp.bfloat16)),
                              np.asarray(b)), path


def test_a_configuration_carries_its_own_rehearsal_sizes():
    cfg = _config("pg_see2017")
    cfg["hparams"]["kv_lora_rank"] = 512  # a width rehearse.json lacks
    cfg["rehearse"] = {"hparams": {"kv_lora_rank": 8, "hidden_dim": 24},
                       "deployment": {"serve": {"serve_slots": 3}},
                       "init": {"stop_bias": -5.0}}
    with open(os.path.join(BENCH, "traffic", "news_open_loop.json")) as f:
        bench_run.apply_rehearsal(cfg, json.load(f), {"check": {}})
    hp, dep = cfg["hparams"], cfg["deployment"]["serve"]
    assert (hp["kv_lora_rank"], hp["hidden_dim"], hp["emb_dim"]) == (8, 24, 8)
    assert dep["serve_slots"] == 3 and dep["serve_buckets"] == "8,16"
    assert cfg["init"]["stop_bias"] == -5.0
    assert cfg["init"]["summary_clock"]["codes"] == 6  # the shared block's


def test_the_tests_shrinkers_take_a_configurations_own_sizes(
        tmp_path, monkeypatch):
    """`tiny_config` and `mid_config` cut the keys they know and take the
    configuration's `rehearse.hparams` for the rest: a width they do not
    know is never run at its published size, and a selection still
    cuts."""
    cfg = _config("pg_see2017")
    cfg["hparams"].update(kv_lora_rank=512, index_topk=2048)
    cfg["rehearse"] = {"hparams": {"kv_lora_rank": 8, "index_topk": 4}}
    os.makedirs(tmp_path / "configs")
    with open(tmp_path / "configs" / "own_widths.json", "w") as f:
        json.dump(cfg, f)
    monkeypatch.setattr(tiny, "BENCH", str(tmp_path))
    for shrunk, sizes in ((tiny.tiny_config("own_widths"), tiny.TINY),
                          (tiny.mid_config("own_widths"), tiny.MID)):
        hp = shrunk["hparams"]
        assert (hp["hidden_dim"], hp["kv_lora_rank"], hp["index_topk"]) == (
            sizes["hidden_dim"], 8, 4)
        assert hp["index_topk"] < hp["max_enc_steps"]  # the selection cuts


def test_stacked_leaves_take_their_fan_in_from_the_last_axis_but_one(
        monkeypatch):
    """Experts stacked on a leading axis are each drawn as a matrix of
    their own: normal(0, gain / sqrt(shape[-2]))."""
    fam = types.ModuleType("harness.families._stacked")
    fam.param_specs = lambda hp: {"dense": ((256, 64), "matrix"),
                                  "experts": ((6, 256, 64), "stacked")}
    monkeypatch.setattr(reference, "family", lambda name: fam)
    tree = weights.make_params({"family": "_stacked", "hparams": {},
                                "init": {"matrix_gain": 2.0}}, 2 ** 31 + 11)
    want = 2.0 / np.sqrt(256)
    assert float(jnp.std(tree["dense"])) == pytest.approx(want, rel=0.03)
    per_expert = np.asarray(jnp.std(tree["experts"], axis=(1, 2)))
    assert per_expert == pytest.approx(np.full(6, want), rel=0.03)


def test_a_familys_own_distributions_replace_the_pointer_mixture():
    """A family with no copy distribution gives `token_logprobs` and
    `next_dist`; scoring and beam search then use them and never ask it
    for an attention or a p_gen."""
    cfg = tiny_config("pg_see2017")
    hp = cfg["hparams"]
    V, n_oov = hp["vocab_size"], hp["max_oov_buckets"]
    pg = reference.family("pointer_generator")
    params = weights.make_params(cfg, 3)
    art = traffic.make_articles(
        {"article": {"length": {"dist": "constant", "value": 6}}}, V, 1, 3)[0]
    outs = [[7, 9, reference.STOP_ID]]
    shared = reference.score_tokens(pg, params, hp, [(art.ids, art.ext)],
                                    outs)

    def token_logprobs(p, hp, ids, ext_ids, n, dec_inputs, targets, mode,
                       act):
        return jnp.full(targets.shape, -2.0)

    def next_dist(p, hp, ids, ext_ids, n, dec_inputs, t, act):
        return jnp.zeros((V + n_oov,)).at[reference.STOP_ID].set(
            0.75).at[5].set(0.25)

    own = types.ModuleType("harness.families._own")
    own.LOG_EPS = 0.0
    own.token_logprobs, own.next_dist = token_logprobs, next_dist
    scored = reference.score_tokens(own, params, hp, [(art.ids, art.ext)],
                                    outs)
    assert scored[0] == pytest.approx(-6.0) and shared[0] != scored[0]
    dist = reference.final_dist_at(
        own, params, hp, jnp.asarray(art.ids), jnp.asarray(art.ext), 6,
        jnp.zeros((2, hp["max_dec_steps"]), jnp.int32), 0)
    assert dist.shape == (2, V + n_oov) and float(dist[1, 3]) == 0.75
