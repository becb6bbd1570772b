"""The comparison that decides `correct`: what the timed path produced
against the plain reference, each number beside a limit of its own (the
limits are data, in the cell's file under benchmark/workloads/, and
PERF.md gives the readings each was set from).

The activations' type is stated on every call of the reference here, apart
from the type the configuration stores its parameters in (`param_dtype`):
the rule is in harness/families/__init__.py.  The SOUND reference keeps
float32 activations for every configuration, over the leaves as they are
stored, each widened where it is read, with matmuls at the chip's default
precision (which on a TPU rounds their inputs to bfloat16 and accumulates
in float32).  The control (`control=True`) puts the reference in the
program's place in the nearest precision below what the configurations
state, bfloat16 leaves AND activations (recurrent state, residual stream,
layer norms, softmax inputs), and must come out as not correct:

  parameters stored    the control's leaves              its activations
  float32              rounded to bfloat16 once, before  bfloat16
                       the program (`low_precision`: a
                       copy of half the tree's size)
  bfloat16             as they are stored                bfloat16

so a bfloat16 configuration's control parts from its sound reference by
the activations alone, and a float32 configuration's reads as it always
has.  `train_numbers` follows the same rule (no training cell states a
`param_dtype` yet).  The program's own lower path
(`compute_dtype=bfloat16`) is read against the same limits by
benchmark/tools/probe.py (PERF.md gives both readings for every number).
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from harness import reference as ref
from harness import weights


def _gap(prog: np.ndarray, refn: np.ndarray, keep: np.ndarray,
         names: Sequence[str]) -> Tuple[float, str]:
    """Worst leaf by |prog norm - ref norm| over max(ref norm of that
    leaf, ref norm of the median leaf)."""
    floor = float(np.median(refn))
    rel = np.abs(prog - refn) / np.maximum(refn, floor)
    rel = np.where(keep, rel, 0.0)
    i = int(np.argmax(rel))
    return float(rel[i]), names[i]


def low_precision(params):
    """The control's leaves: the tree rounded to the control's type.  A
    float32 tree gets one rounded copy, half its size; a bfloat16 tree is
    handed through as it is.  Rounded HERE, before the reference's
    program, and not where a leaf is read: inside one program XLA may
    drop a float32 -> bfloat16 -> float32 round trip (it allows excess
    precision by default), so a cast where a leaf is read is not
    certain to round it; and the training control differentiates at
    these leaves, so that its gradients are accumulated in bfloat16
    across a leaf's uses, as a mixed-precision step keeps them."""
    import jax

    return jax.tree_util.tree_map(lambda x: x.astype(ref.CONTROL), params)


def train_numbers(cfg: Dict[str, Any], seed: int, run, block: int,
                  control: bool = False) -> Dict[str, Any]:
    """The numbers of a training cell: the three losses, the first
    gradient's norm and the parameters' change after three steps, each
    by the worst leaf.  Leaves whose reference gradient is under a
    thousandth of the median leaf's are left out of the change (they
    move under Adagrad by round-off alone)."""
    import jax
    import jax.numpy as jnp

    hp = cfg["hparams"]
    fam = ref.family(cfg["family"])
    p0 = weights.make_params(cfg, seed)
    names = ref.leaf_names(p0)

    def loss_grad(act, read=lambda q: q):
        """(parameters, rows) -> (loss, gradients) with the activations
        in `act`, differentiated at `read(parameters)`; the gradients
        come back in the type the parameters are stored in."""
        def fn(q, a):
            loss, g = jax.value_and_grad(lambda x: ref.batch_loss(
                fam, x, hp, a, act=act))(read(q))
            return loss.astype(jnp.float32), jax.tree_util.tree_map(
                lambda x, y: y.astype(x.dtype), q, g)
        return fn

    sound = loss_grad(ref.SOUND)
    r_loss, r_g1, r_d3 = ref.train_steps(fam, p0, hp, run.batches, block,
                                         sound)
    if control == "half_batch":
        # a fault, planted in the reference put in the program's place:
        # half of each batch left out, the mean taken over the rest
        halves = [{k: v[:v.shape[0] // 2] for k, v in b.items()}
                  for b in run.batches]
        prog_loss, prog_g1, prog_d3 = ref.train_steps(
            fam, p0, hp, halves, block, sound)
    elif control:
        # the reference in the program's place, forward and backward in
        # bfloat16 (master weights and optimizer state stay as they are
        # stored, as mixed-precision training keeps them)
        prog_loss, prog_g1, prog_d3 = ref.train_steps(
            fam, p0, hp, run.batches, block,
            loss_grad(ref.CONTROL, low_precision))
    else:
        prog_loss, prog_g1, prog_d3 = run.losses, run.g1, run.d3
    r_loss = np.asarray(r_loss)
    loss_gap = float(np.max(np.abs(np.asarray(prog_loss[:len(r_loss)])
                                   - r_loss) / np.abs(r_loss)))
    everything = np.ones_like(r_g1, bool)
    grad_gap, grad_leaf = _gap(np.asarray(prog_g1), r_g1, everything, names)
    moved = r_g1 >= 1e-3 * float(np.median(r_g1))
    upd_gap, upd_leaf = _gap(np.asarray(prog_d3), r_d3, moved, names)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "update_norm_gap": upd_gap,
            "_detail": {"losses": [float(x) for x in prog_loss[:3]],
                        "ref_losses": [float(x) for x in r_loss],
                        "grad_leaf": grad_leaf, "update_leaf": upd_leaf,
                        "leaves_left_out": [n for n, m in zip(names, moved)
                                            if not m]}}


def served_tokens(words_lib, article, result, hp) -> Tuple[List[int], int]:
    """(the served hypothesis' generated tokens with STOP restored where
    the search stopped, the length the program normalised by)."""
    toks = words_lib.ids_of(result.decoded_words, article.words)
    if len(toks) < int(hp["max_dec_steps"]):
        toks = toks + [ref.STOP_ID]
    return toks, len(toks) + 1


def pick_sample(finished: Sequence[Any], n: int, seed: int) -> List[Any]:
    """n finished requests drawn from the seed, the longest output among
    them."""
    from harness.traffic import rng_for

    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][1].decoded_words))
    idx = [longest] + [int(i) for i in rng_for(seed, 7).permutation(
        len(finished)) if int(i) != longest][:max(0, n - 1)]
    return [finished[i] for i in idx]


def serve_numbers(cfg: Dict[str, Any], seed: int, finished: Sequence[Any],
                  words_lib, sample: Dict[str, int],
                  control: bool = False) -> Dict[str, Any]:
    """The numbers of a served cell, over a sample of the requests the
    window finished:
      score_gap  widest |served avg_log_prob - the reference's
                 length-normalised log probability of the served tokens|
                 (encoder/prefill, slot step through the arena, the copy
                 mixture, the length normalisation)
      beam_gap   widest amount by which the reference's score of the
                 served tokens lies BELOW the best hypothesis of the
                 reference's own beam search (beam bookkeeping: one
                 answer altered)
      beam_gap_median  the median of those amounts over the searches.
                 Two sound searches part where two candidates tie to
                 rounding at a pruning, and end a few thousandths apart
                 (3% of searches, PERF.md): the widest swings with that
                 from seed to seed, the median does not, so it is held
                 tightly and the widest loosely
    The two beam numbers are there only where the cell samples a search
    (`check.sample.beam` >= 1): one cacheless search of a large model can
    cost more than the window, and a number that was not measured is left
    out, so that `judge` fails a limit kept for it.
    """
    hp = cfg["hparams"]
    fam = ref.family(cfg["family"])
    params = weights.make_params(cfg, seed)
    picked = pick_sample(finished, int(sample["score"]), seed)
    arts = [(a.ids, a.ext) for a, _ in picked]
    outs, lens = [], []
    for a, r in picked:
        t, n = served_tokens(words_lib, a, r, hp)
        outs.append(t)
        lens.append(n)
    lens = np.asarray(lens, np.float64)
    r_avg = ref.score_tokens(fam, params, hp, arts, outs,
                             act=ref.SOUND) / lens
    beam_gaps = []
    for (a, _), avg in list(zip(picked, r_avg))[:int(sample["beam"])]:
        _, best = ref.beam_search(fam, params, hp, a.ids, a.ext,
                                  act=ref.SOUND)
        beam_gaps.append(float(best - avg))
    if control:
        served = ref.score_tokens(fam, low_precision(params), hp, arts,
                                  outs, act=ref.CONTROL) / lens
    else:
        served = np.asarray([r.avg_log_prob for _, r in picked])
    gaps = np.abs(served - r_avg)
    i = int(np.argmax(gaps))
    numbers = {"score_gap": float(gaps[i])}
    if beam_gaps:
        numbers.update(beam_gap=float(max(beam_gaps)),
                       beam_gap_median=float(statistics.median(beam_gaps)))
    numbers["_detail"] = {"sampled": len(picked),
                          "served_tokens": int(sum(len(o) for o in outs)),
                          "worst_uuid": picked[i][0].uuid,
                          "worst_served": float(served[i]),
                          "worst_reference": float(r_avg[i]),
                          "beam_gaps": beam_gaps}
    return numbers


def judge(numbers: Dict[str, Any], limits: Dict[str, float],
          extra_ok: bool = True) -> Tuple[bool, Dict[str, Any]]:
    """Each number beside its limit; correct only if every number that
    has a limit is finite and within it."""
    compared = {}
    ok = bool(extra_ok)
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        compared[name] = {"value": v, "limit": limit}
        ok = ok and bool(good)
    return ok, compared
