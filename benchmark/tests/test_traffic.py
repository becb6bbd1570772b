"""The generator: deterministic in --seed, the same multiset of work for
every seed, the benchmark's own tokenisation equal to the program's."""
import json
import os

import numpy as np

from harness import traffic
from tests.tiny import BENCH


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_inputs_other_seed_same_sizes():
    mix = _mix("news_open_loop")
    big = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits
    a = traffic.make_articles(mix, 50000, 200, big)
    b = traffic.make_articles(mix, 50000, 200, big)
    c = traffic.make_articles(mix, 50000, 200, 7)
    assert [x.text for x in a] == [x.text for x in b]
    assert [x.text for x in a] != [x.text for x in c]
    assert sorted(len(x.ids) for x in a) == sorted(len(x.ids) for x in c)
    lens = np.array([len(x.ids) for x in a])
    assert lens.min() >= 50 and lens.max() == 400
    assert 0.5 < np.mean(lens == 400) < 0.7  # about 61% are cut at 400
    assert np.mean(lens < 200) > 0.08  # every serve bucket is hit
    oa = traffic.arrival_offsets(mix, 200, big)
    ob = traffic.arrival_offsets(mix, 200, big)
    oc = traffic.arrival_offsets(mix, 200, 7)
    assert np.array_equal(oa, ob) and not np.array_equal(oa, oc)
    assert np.allclose(np.sort(traffic.arrival_gaps(mix, 200, big)),
                       np.sort(traffic.arrival_gaps(mix, 200, 7)))
    assert oa[0] == 0 and abs(oa[-1] - 199 / mix["rate_per_s"]) < 0.2


def test_summary_lengths_are_coded_in_the_first_word():
    """Every seed asks for the same multiset of summary lengths (the
    mix's quantiles) through the first words of its articles, and the
    weights' code for a word is the generator's."""
    from harness import weights

    mix = _mix("news_open_loop")
    with open(os.path.join(BENCH, "configs", "pg_see2017.json")) as f:
        fam, clock = weights.summary_clock(json.load(f))
    a = traffic.make_articles(mix, 50000, 300, 2 ** 31 + 9,
                              clock=(fam, clock))
    b = traffic.make_articles(mix, 50000, 300, 11, clock=(fam, clock))

    def coded(arts):
        return sorted(int(fam.length_code(clock, int(x.ids[0])))
                      for x in arts)

    assert coded(a) == coded(b)
    want = np.array(coded(a))
    assert want.min() == 36 and want.max() <= 100
    assert 54 <= want.mean() <= 58  # See et al.: 56 tokens on average
    # chunks of 25 decode steps: two, three and four chunks all occur
    assert all(np.mean((want > lo) & (want <= hi)) > 0.05
               for lo, hi in ((35, 50), (50, 75), (75, 100)))
    # the first word is a vocabulary word, and the article lengths are
    # those of the mix without the clock
    plain = traffic.make_articles(mix, 50000, 300, 11)
    assert [len(x.ids) for x in b] == [len(x.ids) for x in plain]
    assert all(x.words[0].startswith("w") and x.ids[0] == x.ext[0]
               for x in b)


def test_tokenisation_equals_the_programs():
    from textsummarization_on_flink_tpu.config import HParams
    from textsummarization_on_flink_tpu.data.batching import SummaryExample
    from textsummarization_on_flink_tpu.data.vocab import Vocab

    mix = _mix("news_open_loop")
    mix["article"]["oov_share"] = 0.2
    V = 500
    words = traffic.Words(V, mix["article"])
    vocab = Vocab(words=words.vocabulary())
    assert vocab.size() == V
    hps = HParams(vocab_size=V, max_enc_steps=400)
    from harness import reference

    clock = (reference.family("pointer_generator"),
             {"codes": 65, "min_tokens": 36})
    for art in (traffic.make_articles(mix, V, 5, 3)
                + traffic.make_articles(mix, V, 5, 4, clock=clock)):
        ex = SummaryExample.build(art.text, [], vocab, hps)
        assert ex.enc_input == list(art.ids)
        assert ex.enc_input_extend_vocab == list(art.ext)
        out = art.words[:5] + ["[UNK]"]
        assert words.ids_of(out, art.words) == list(art.ext[:5]) + [0]


def test_training_rows_and_percentile():
    mix = _mix("cnndm_b64")
    mix["dataset_rows"] = 32
    rows = traffic.make_training_rows(mix, 50000, 5)
    assert rows == traffic.make_training_rows(mix, 50000, 5)
    for art, abstract in rows:
        n = len(abstract.split()) - 2
        assert 35 <= n <= 99 and 50 <= len(art.split()) <= 400
    assert traffic.percentile([1, 2, 3, 4, float("inf")], 50) == 3
    assert traffic.percentile(list(range(1, 101)), 95) == 95
