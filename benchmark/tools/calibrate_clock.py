"""Builder's tool (CPU, counts only): the summary lengths the plain
reference's beam search gives on seed-made weights with the summary clock
(the family's `wire`), against the lengths the articles' first words ask
for, for a few STOP biases, at the configuration's full widths.  From the
root:

    JAX_PLATFORMS=cpu python benchmark/tools/calibrate_clock.py <config> <stop_bias> [...]

N_ART articles (default 8) of the mix news_open_loop, SEEDS weight seeds
(default "1,2"); INIT='{"key": value}' overrides entries of the clock.
"""
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import reference as ref  # noqa: E402
from harness import traffic, weights  # noqa: E402


def main():
    name, biases = sys.argv[1], [float(x) for x in sys.argv[2:]]
    n_art = int(os.environ.get("N_ART", "8"))
    seeds = [int(x) for x in os.environ.get("SEEDS", "1,2").split(",")]
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", "news_open_loop.json")) as f:
        mix = json.load(f)
    fam, clock = weights.summary_clock(cfg)
    clock.update(json.loads(os.environ.get("INIT", "{}")))
    V = cfg["hparams"]["vocab_size"]
    for b in biases:
        cfg["init"]["stop_bias"] = b
        for seed in seeds:
            params = weights.make_params(cfg, seed)
            arts = traffic.make_articles(mix, V, n_art, seed,
                                          clock=(fam, clock))
            want = [int(fam.length_code(clock, int(a.ids[0])))
                    for a in arts]
            got = [len(ref.beam_search(fam, params, cfg["hparams"], a.ids,
                                       a.ext)[0]) for a in arts]
            print(json.dumps({"config": name, "stop_bias": b, "seed": seed,
                              "wanted": want, "got": got,
                              "mean_diff": float(np.mean(
                                  np.array(got) - np.array(want)))}),
                  flush=True)


if __name__ == "__main__":
    main()
