"""The one general traffic generator.  A mix is a data file under
benchmark/traffic/; this module reads its parameters and makes, from
--seed, the articles, their arrival times, and (for a training job) the
dataset rows.

Every seed gets the SAME multiset of article lengths, summary lengths and
inter-arrival gaps (the quantiles of the mix's distributions), in another
order and with other words: the seed changes the order of the work, not
its amount.

Words: the vocabulary is `w0 .. w<V-5>` after the four special tokens, so
word `w<i>` has id i + 4; word ranks follow a Zipf law; a share of the
positions holds out-of-vocabulary words `oov<j>`, which get the ids
V, V+1, ... in order of first appearance in their article (See et al.).
The benchmark keeps this tokenisation itself, so the reference is fed
nothing the program has made.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

N_SPECIAL = 4


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose; --seed may exceed 2**31."""
    return np.random.default_rng([int(seed), int(stream)])


def quantile_lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """n lengths at the evenly spaced quantiles of a clipped lognormal
    (or a constant): the same multiset for every seed."""
    if spec["dist"] == "constant":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    q = (np.arange(n) + 0.5) / n
    z = np.array([nd.inv_cdf(float(x)) for x in q])
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def quantile_gaps(n: int, rate: float, kind: str) -> np.ndarray:
    """n inter-arrival gaps with mean 1/rate: the quantiles of the
    exponential law (Poisson arrivals), or equal gaps."""
    if kind == "uniform":
        return np.full(n, 1.0 / rate)
    if kind != "poisson":
        raise ValueError(f"unknown arrival process {kind!r}")
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    return gaps * (n / rate) / gaps.sum()  # exact mean 1/rate


def _zipf_cdf(n_words: int, s: float) -> np.ndarray:
    w = 1.0 / np.power(np.arange(1, n_words + 1, dtype=np.float64), s)
    return np.cumsum(w / w.sum())


class Words:
    """Word ranks -> words and ids for one vocabulary size."""

    def __init__(self, vocab_size: int, spec: Dict[str, Any]):
        self.V = int(vocab_size)
        self.n_words = self.V - N_SPECIAL
        self.cdf = _zipf_cdf(self.n_words, float(spec.get("zipf_s", 1.0)))
        self.oov_share = float(spec.get("oov_share", 0.01))
        self.oov_pool = int(spec.get("oov_pool", 1000))
        self.max_oov = int(spec.get("max_oov_buckets", 128))

    def vocabulary(self) -> List[str]:
        return [f"w{i}" for i in range(self.n_words)]

    def draw(self, rng: np.random.Generator, n: int,
             ) -> Tuple[List[str], np.ndarray, np.ndarray]:
        """n words: (words, fixed-vocabulary ids with OOV as UNK=0,
        extended ids with in-article OOVs numbered from V)."""
        ranks = np.searchsorted(self.cdf, rng.random(n))
        is_oov = rng.random(n) < self.oov_share
        oov_j = rng.integers(0, self.oov_pool, size=n)
        words: List[str] = []
        ids = np.empty(n, np.int32)
        ext = np.empty(n, np.int32)
        seen: Dict[int, int] = {}
        for i in range(n):
            if is_oov[i]:
                j = int(oov_j[i])
                k = seen.setdefault(j, len(seen))
                words.append(f"oov{j}")
                ids[i] = 0
                ext[i] = self.V + k if k < self.max_oov else 0
            else:
                words.append(f"w{ranks[i]}")
                ids[i] = ext[i] = ranks[i] + N_SPECIAL
        return words, ids, ext

    def ids_of(self, words: Sequence[str], article_words: Sequence[str],
               ) -> List[int]:
        """Extended ids of output words against their article (the
        inverse of the program's outputids2words)."""
        oovs: Dict[str, int] = {}
        for w in article_words:
            if w.startswith("oov") and w not in oovs:
                oovs[w] = len(oovs)
        out = []
        for w in words:
            if w.startswith("w") and w[1:].isdigit():
                out.append(int(w[1:]) + N_SPECIAL)
            elif w in oovs:
                out.append(self.V + oovs[w])
            else:
                out.append({"[UNK]": 0, "[PAD]": 1, "[START]": 2,
                            "[STOP]": 3}[w])
        return out


class Article:
    __slots__ = ("uuid", "text", "words", "ids", "ext")

    def __init__(self, uuid, words, ids, ext):
        self.uuid = uuid
        self.words = words
        self.text = " ".join(words)
        self.ids = ids
        self.ext = ext


def make_articles(mix: Dict[str, Any], vocab_size: int, n: int, seed: int,
                  prefix: str = "r", clock=(None, None),
                  ) -> List[Article]:
    """n articles for a serving mix: lengths are the mix's quantiles in a
    seed-drawn order, words drawn from the seed.

    Where the mix asks for summary lengths (`summary.length`) and the
    configuration's weights carry a summary clock (`clock`: the pair
    `weights.summary_clock(cfg)` gives), each article's FIRST word is
    moved to the word of the same Zipf neighbourhood that codes for the
    length wanted (the family's `word_for_length`): the summary lengths
    are the mix's quantiles too, in an order of their own."""
    words = Words(vocab_size, mix["article"])
    order_rng, word_rng = rng_for(seed, 1), rng_for(seed, 2)
    lengths = quantile_lengths(mix["article"]["length"], n)
    order_rng.shuffle(lengths)
    wanted = None
    fam, clock = clock
    if mix.get("summary") and clock:
        wanted = quantile_lengths(mix["summary"]["length"], n)
        rng_for(seed, 4).shuffle(wanted)
    out = []
    for i, L in enumerate(lengths):
        w, ids, ext = words.draw(word_rng, int(L))
        if wanted is not None:
            # a Zipf rank whatever the draw put first (never an OOV word)
            rank = int(np.searchsorted(words.cdf, word_rng.random()))
            rank = fam.word_for_length(clock, int(wanted[i]), rank,
                                       words.n_words)
            w[0], ids[0], ext[0] = f"w{rank}", rank + N_SPECIAL, \
                rank + N_SPECIAL
            if any(x.startswith("oov") for x in w[1:]):
                # OOV ids count from the first OOV word: renumber
                seen: Dict[str, int] = {}
                for j, x in enumerate(w):
                    if x.startswith("oov"):
                        k = seen.setdefault(x, len(seen))
                        ext[j] = words.V + k if k < words.max_oov else 0
        out.append(Article(f"{prefix}{i:06d}", w, ids, ext))
    return out


def arrival_gaps(mix: Dict[str, Any], n: int, seed: int) -> np.ndarray:
    """The mix's n inter-arrival gaps in the seed's order."""
    gaps = quantile_gaps(n, float(mix["rate_per_s"]),
                         mix.get("arrivals", "poisson"))
    rng_for(seed, 3).shuffle(gaps)
    return gaps


def arrival_offsets(mix: Dict[str, Any], n: int, seed: int) -> np.ndarray:
    """Due times in seconds from the start of the window (the first
    request is due at once)."""
    gaps = arrival_gaps(mix, n, seed)
    return np.cumsum(gaps) - gaps[0]


def make_training_rows(mix: Dict[str, Any], vocab_size: int, seed: int,
                       ) -> List[Tuple[str, str]]:
    """(article text, abstract text with <s> </s> sentence marks) rows of
    a training job's dataset.  A share of each abstract's words is copied
    from its article (out-of-vocabulary words among them), so that the
    copy path carries part of the loss."""
    n = int(mix["dataset_rows"])
    words = Words(vocab_size, mix["article"])
    order_rng, word_rng = rng_for(seed, 1), rng_for(seed, 2)
    art_len = quantile_lengths(mix["article"]["length"], n)
    abs_len = quantile_lengths(mix["abstract"]["length"], n)
    order_rng.shuffle(art_len)
    order_rng.shuffle(abs_len)
    copy_share = float(mix["abstract"].get("copy_share", 0.5))
    rows = []
    for L, A in zip(art_len, abs_len):
        art, _, _ = words.draw(word_rng, int(L))
        fresh, _, _ = words.draw(word_rng, int(A))
        take = word_rng.random(int(A)) < copy_share
        src = word_rng.integers(0, int(L), size=int(A))
        abstract = [art[int(s)] if t else w
                    for w, t, s in zip(fresh, take, src)]
        rows.append((" ".join(art), "<s> " + " ".join(abstract) + " </s>"))
    return rows


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by the nearest-rank rule on ALL the
    values given; +inf entries (requests that never finished) rank last."""
    v = sorted(values)
    if not v:
        return float("nan")
    k = min(len(v) - 1, max(0, math.ceil(q / 100.0 * len(v)) - 1))
    return v[k]
