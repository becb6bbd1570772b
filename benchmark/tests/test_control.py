"""The control at a size a test run can hold: the plain reference put in
the program's place in bfloat16 (parameters and activations) must fail
at least one of the limits its cell is held to (on the chip it was read
at the cell's own size: PERF.md).  The cells are BENCHMARK.json's."""
import pytest

from harness import correct, traffic, weights
from harness import reference as ref
from tests.tiny import (cell_file, cells, mid_batches, mid_config,
                        traffic_of)


def _limits(cell):
    return cell_file(cell)["limits"]


class _Run:
    pass


@pytest.mark.parametrize(
    "cell,name", cells("train_job") + cells("train_job", held=True))
def test_training_control_fails_the_limits(cell, name):
    """Held cells too: `tf_train` was held because its control did not
    fail, and at this size that was the transformer reference's fault,
    not the cell's (a NumPy scalar kept the control's residual stream in
    float32: PERF.md section 6, PR 36).  The chip's reading of the
    repaired control is what brings the cell back."""
    cfg = mid_config(name)
    run = _Run()
    run.batches = mid_batches(cfg["hparams"])
    numbers = correct.train_numbers(cfg, 3, run, block=4, control=True)
    numbers["compiles_in_window"] = 0
    ok, compared = correct.judge(numbers, _limits(cell))
    assert not ok, compared
    fault = correct.train_numbers(cfg, 3, run, block=4, control="half_batch")
    fault["compiles_in_window"] = 0
    assert not correct.judge(fault, _limits(cell))[0]


def _served_by_the_reference(cell, name, n):
    """(cfg, words, clock, finished, tokens): n articles at the middle
    size, each "served" by the reference's own beam search.  Where the
    configuration's weights carry a summary clock, the family's clock of
    that size (`MID_CLOCK`) is laid over it and the mix asks for the
    lengths it codes; elsewhere `clock` is None, the mix has no `summary`
    block and every summary runs to `max_dec_steps`, as on the chip.
    The articles hold out-of-vocabulary words only where the cell's own
    traffic does, and then at 2% or the cell's share, whichever is more:
    so few and so short articles would otherwise seldom carry one, and
    the copy path over extended ids is what they are there for."""
    cfg = mid_config(name)
    hp = cfg["hparams"]
    fam = ref.family(cfg["family"])
    share = float(traffic_of(cell)["article"].get("oov_share", 0.0))
    mix = {"article": {"length": {"dist": "lognormal", "median": 60,
                                  "sigma": 0.5, "min": 16, "max": 64},
                       "oov_share": max(share, 0.02) if share else 0.0,
                       "oov_pool": 50, "max_oov_buckets": 8}}
    clock = None
    if "summary_clock" in cfg["init"]:
        cfg["init"].update(fam.MID_CLOCK)
        clock = cfg["init"]["summary_clock"]
        lo = int(clock["min_tokens"])
        mix["summary"] = {"length": {
            "dist": "lognormal", "median": 12, "sigma": 0.4, "min": lo,
            "max": min(lo + int(clock["codes"]) - 1, hp["max_dec_steps"])}}
    words = traffic.Words(hp["vocab_size"], mix["article"])
    params = weights.make_params(cfg, 3)

    class Res:
        pass

    finished, tokens = [], []
    articles = traffic.make_articles(mix, hp["vocab_size"], n, 3,
                                     clock=weights.summary_clock(cfg))
    copied = sum(int(x) >= hp["vocab_size"] for a in articles for x in a.ext)
    assert (copied > 0) == (share > 0), copied
    for art in articles:
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


@pytest.mark.parametrize("cell,name", cells("open_loop"))
def test_serving_control_fails_the_limits(cell, name):
    cfg, words, clock, finished, tokens = _served_by_the_reference(
        cell, name, 6)
    if clock is not None:
        # the clock works: each summary ends near the length its
        # article's first word codes for
        fam = ref.family(cfg["family"])
        off = [len(toks) - int(fam.length_code(clock, int(art.ids[0])))
               for (art, _), toks in zip(finished, tokens)]
        assert max(abs(x) for x in off) <= 3, off
    # a search of the reference's own only where the cell samples one
    sample = {"score": 6,
              "beam": min(1, int(cell_file(cell)["check"]["sample"]["beam"]))}
    sound = correct.serve_numbers(cfg, 3, finished, words, sample)
    sound["compiles_in_window"] = 0
    assert correct.judge(sound, _limits(cell))[0], sound
    control = correct.serve_numbers(cfg, 3, finished, words, sample,
                                    control=True)
    control["compiles_in_window"] = 0
    assert not correct.judge(control, _limits(cell))[0], control


BEAM_NUMBERS = ("beam_gap", "beam_gap_median")
_BEAM_CELLS = [c for c in cells("open_loop")
               if set(BEAM_NUMBERS) & set(_limits(c[0]))]
# the beam's bookkeeping stays under test: an accepted cell holds it
assert _BEAM_CELLS, "no serving cell of BENCHMARK.json holds a beam number"


@pytest.mark.parametrize("altered,fails", [(1, ("beam_gap",)),
                                           (5, BEAM_NUMBERS)])
@pytest.mark.parametrize("cell,name", _BEAM_CELLS)
def test_an_altered_answer_fails_a_beam_number(cell, name, altered, fails):
    """One answer of five that is not the search's best (three of its
    words altered) fails the widest gap and leaves the median where it
    was; all five fail both."""
    cfg, words, _, finished, _ = _served_by_the_reference(cell, name, 5)
    for _, r in finished[:altered]:
        r.decoded_words[1:4] = ["w7", "w8", "w9"]
    sample = {"score": 5, "beam": 5}
    numbers = correct.serve_numbers(cfg, 3, finished, words, sample)
    limits = _limits(cell)
    over = tuple(k for k in BEAM_NUMBERS if numbers[k] > limits[k])
    assert over == fails, numbers
