"""The cost of one ``Profiler.phase`` / ``Profiler.wall`` bracket, enter
plus exit, with no profiler capture running: what every phase on the
serving hot path adds to an untraced run.

    python scripts/bracket_cost.py [--n 20000] [--repeats 7]

jax is imported first, as in a server, so the bracket opens a real
``jax.profiler.TraceAnnotation``.  Prints one JSON line of microseconds a
bracket (the median over `repeats` timings of `n` brackets each, the
empty loop's time taken out): a top-level phase, a child phase opened
inside one, and a wall.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402,F401  (the bracket annotates only where jax is loaded)

from textsummarization_on_flink_tpu.obs import profile as profile_lib  # noqa: E402
from textsummarization_on_flink_tpu.obs.registry import Registry  # noqa: E402


def _per_bracket_us(body, n: int, repeats: int) -> float:
    def timed(fn):
        t0 = time.perf_counter()
        fn(n)
        return time.perf_counter() - t0

    def empty(k):
        for _ in range(k):
            pass

    body(n)  # first use creates the labelled series and the span ring
    runs = [timed(body) - timed(empty) for _ in range(repeats)]
    return statistics.median(runs) / n * 1e6


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--repeats", type=int, default=7)
    args = ap.parse_args()
    prof = profile_lib.install_profiler(Registry())

    def phase(k):
        for _ in range(k):
            with prof.phase("serve/harvest/unpack", slot=3):
                pass

    def child(k):
        with prof.phase("serve/harvest"):
            for _ in range(k):
                with prof.phase("serve/harvest/unpack", slot=3):
                    pass

    def wall(k):
        for _ in range(k):
            with prof.wall("serve/tick"):
                pass

    out = {"phase_us": _per_bracket_us(phase, args.n, args.repeats),
           "child_phase_us": _per_bracket_us(child, args.n, args.repeats),
           "wall_us": _per_bracket_us(wall, args.n, args.repeats),
           "n": args.n, "repeats": args.repeats,
           "backend": jax.default_backend()}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
