"""The control at a size a test run can hold: the plain reference put in
the program's place in bfloat16 (parameters and activations) must fail
at least one of the limits its cell is held to (on the chip it was read
at the cell's own size: PERF.md).  The cells are BENCHMARK.json's."""
import glob
import json
import os

import numpy as np
import pytest

from harness import correct, traffic, weights
from harness import reference as ref
from tests.tiny import BENCH, tiny_config

MID = {"hidden_dim": 64, "emb_dim": 32, "vocab_size": 2000,
       "max_enc_steps": 64, "max_dec_steps": 24, "beam_size": 2,
       "min_dec_steps": 4, "max_oov_buckets": 8, "num_heads": 4,
       "ffn_dim": 128, "enc_layers": 2, "dec_layers": 2}


ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    _B = json.load(_f)
_HELD = [json.load(open(p)) for p in sorted(glob.glob(
    os.path.join(BENCH, "held", "*.json")))]


def _cells(kind, held=False):
    """(cell, configuration) of BENCHMARK.json's cells (or of the held
    cells, benchmark/held/) whose mix is of `kind`."""
    out = []
    for w in ([h["workload"] for h in _HELD] if held else _B["workloads"]):
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            if json.load(f)["kind"] == kind:
                out.append((w["name"], w["config"]))
    return out


def _limits(cell):
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        return json.load(f)["limits"]


def _mid(name):
    cfg = tiny_config(name)
    cfg["hparams"].update({k: v for k, v in MID.items()
                           if k in cfg["hparams"]})
    return cfg


class _Run:
    pass


@pytest.mark.parametrize("cell,name,held", [
    c + (False,) for c in _cells("train_job")] + [
    c + (True,) for c in _cells("train_job", held=True)])
def test_training_control_fails_the_limits(cell, name, held):
    cfg = _mid(name)
    hp = cfg["hparams"]
    rng = np.random.RandomState(0)
    B, Te, Td, V = 8, hp["max_enc_steps"], hp["max_dec_steps"], hp["vocab_size"]
    batches = []
    for _ in range(3):
        ids = rng.randint(4, V, (B, Te)).astype(np.int32)
        tgt = rng.randint(4, V, (B, Td)).astype(np.int32)
        tgt[:, ::2] = ids[:, :Td:2][:, :tgt[:, ::2].shape[1]]
        batches.append({
            "enc_batch": ids, "enc_batch_extend_vocab": ids,
            "enc_lens": np.full((B,), Te, np.int32),
            "enc_padding_mask": np.ones((B, Te), np.float32),
            "dec_batch": np.concatenate(
                [np.full((B, 1), 2, np.int32), tgt[:, :-1]], 1),
            "target_batch": tgt,
            "dec_padding_mask": np.ones((B, Td), np.float32)})
    run = _Run()
    run.batches = batches
    numbers = correct.train_numbers(cfg, 3, run, block=4, control=True)
    numbers["compiles_in_window"] = 0
    ok, compared = correct.judge(numbers, _limits(cell))
    # a held cell is held because its control does NOT fail (PERF.md
    # section 7); the day it does, the cell can go back
    assert ok if held else not ok, compared
    fault = correct.train_numbers(cfg, 3, run, block=4, control="half_batch")
    fault["compiles_in_window"] = 0
    assert not correct.judge(fault, _limits(cell))[0]


def _served_by_the_reference(name, n):
    """(cfg, words, clock, finished, tokens): n articles at the middle
    size, each "served" by the reference's own beam search."""
    cfg = _mid(name)
    # the summary clock at this size: lengths 5..24 (families/pointer_generator.py)
    cfg["init"]["stop_bias"] = -12.6
    clock = cfg["init"]["summary_clock"] = {
        "units": 4, "gain": 24.0, "step": 0.03, "phase": 0.002,
        "c_star": 1.0, "codes": 20, "min_tokens": 5}
    hp = cfg["hparams"]
    mix = {"article": {"length": {"dist": "lognormal", "median": 60,
                                  "sigma": 0.5, "min": 16, "max": 64},
                       "oov_share": 0.02, "oov_pool": 50,
                       "max_oov_buckets": 8},
           "summary": {"length": {"dist": "lognormal", "median": 12,
                                  "sigma": 0.4, "min": 5, "max": 24}}}
    words = traffic.Words(hp["vocab_size"], mix["article"])
    fam = ref.family(cfg["family"])
    params = weights.make_params(cfg, 3)

    class Res:
        pass

    finished, tokens = [], []
    for art in traffic.make_articles(mix, hp["vocab_size"], n, 3,
                                     clock=weights.summary_clock(cfg)):
        toks, avg = ref.beam_search(fam, params, hp, art.ids, art.ext)
        tokens.append(toks)
        r = Res()
        out = [t for t in toks if t != ref.STOP_ID]
        r.decoded_words = [f"w{t - 4}" if 4 <= t < hp["vocab_size"]
                           else "[UNK]" if t < 4 else
                           [w for w in dict.fromkeys(art.words)
                            if w.startswith("oov")][t - hp["vocab_size"]]
                           for t in out]
        r.avg_log_prob = avg
        finished.append((art, r))
    return cfg, words, clock, finished, tokens


@pytest.mark.parametrize("cell,name", _cells("open_loop"))
def test_serving_control_fails_the_limits(cell, name):
    cfg, words, clock, finished, tokens = _served_by_the_reference(name, 6)
    fam = ref.family(cfg["family"])
    off = [len(toks) - int(fam.length_code(clock, int(art.ids[0])))
           for (art, _), toks in zip(finished, tokens)]
    # the clock works: each summary ends near the length its article's
    # first word codes for
    assert max(abs(x) for x in off) <= 3, off
    sample = {"score": 6, "beam": 1}
    sound = correct.serve_numbers(cfg, 3, finished, words, sample)
    sound["compiles_in_window"] = 0
    assert correct.judge(sound, _limits(cell))[0], sound
    control = correct.serve_numbers(cfg, 3, finished, words, sample,
                                    control=True)
    control["compiles_in_window"] = 0
    assert not correct.judge(control, _limits(cell))[0], control


@pytest.mark.parametrize("altered,fails", [(1, ("beam_gap",)),
                                           (5, ("beam_gap",
                                                "beam_gap_median"))])
@pytest.mark.parametrize("cell,name", _cells("open_loop"))
def test_an_altered_answer_fails_a_beam_number(cell, name, altered, fails):
    """One answer of five that is not the search's best (three of its
    words altered) fails the widest gap and leaves the median where it
    was; all five fail both."""
    cfg, words, _, finished, _ = _served_by_the_reference(name, 5)
    for _, r in finished[:altered]:
        r.decoded_words[1:4] = ["w7", "w8", "w9"]
    sample = {"score": 5, "beam": 5}
    numbers = correct.serve_numbers(cfg, 3, finished, words, sample)
    limits = _limits(cell)
    over = tuple(k for k in ("beam_gap", "beam_gap_median")
                 if numbers[k] > limits[k])
    assert over == fails, numbers
