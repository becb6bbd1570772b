"""What the plain references share: the pointer mixture, the loss,
gradients in blocks of rows, TF1 Adagrad with global-norm clipping, the
scoring of served tokens, and See et al.'s beam search in plain Python.
Imports nothing of the program.

`family(name)` returns the module harness/families/<name>.py of a
configuration's "family": its `encode` / `decode` / `LOG_EPS` feed the
pointer mixture here, unless the family gives `token_logprobs` /
`next_dist` of its own (no copy distribution, a head over a slice of the
vocabulary).  All functions take `hp`, the config file's "hparams" dict,
and `act`, the type of the activations (the rule is in
families/__init__.py): SOUND unless the caller states the CONTROL's.
The parameters go into every jitted program in the type they are stored
in, and a leaf is cast where the family reads it: no copy of the tree in
another type is ever made.
"""

from __future__ import annotations

import functools
import importlib
import json
import os
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.families import CONTROL, SOUND  # noqa: F401  (callers' names)

UNK_ID, PAD_ID, START_ID, STOP_ID = 0, 1, 2, 3


def family(name: str):
    """The module of a configuration's "family"; a name with no file
    under harness/families/ is an error that lists the files there."""
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "families")
    have = sorted(f[:-3] for f in os.listdir(here)
                  if f.endswith(".py") and not f.startswith("_"))
    if name not in have:
        raise ValueError(f"no model family {name!r}: benchmark/harness/"
                       f"families/ holds {have}")
    return importlib.import_module("harness.families." + name)


# ---------------------------------------------------------------- mixture

def _token_logprobs(fam, p, hp, ids, ext_ids, n, dec_inputs, targets,
                    decode_mode, act):
    """log P(targets[t]) at every position, for one article: the
    family's own, else the pointer mixture.  ids/ext_ids [T],
    dec_inputs/targets [Td]."""
    if hasattr(fam, "token_logprobs"):
        return fam.token_logprobs(p, hp, ids, ext_ids, n, dec_inputs,
                                  targets, decode_mode, act)
    V = int(hp["vocab_size"])
    enc = fam.encode(p, hp, ids, n, act)
    proj_in, W, b, att, pgen = fam.decode(p, hp, enc, dec_inputs, decode_mode,
                                          act)
    scores = (proj_in @ W + b).astype(jnp.float32)  # [Td, V]
    lse = jax.scipy.special.logsumexp(scores, -1)
    in_vocab = targets < V
    score_t = jnp.take_along_axis(
        scores, jnp.where(in_vocab, targets, 0)[:, None], -1)[:, 0]
    gen = jnp.where(in_vocab, jnp.exp(score_t - lse), 0.0)
    valid = jnp.arange(ids.shape[0]) < n
    copy = jnp.sum(att.astype(jnp.float32)
                   * ((ext_ids[None, :] == targets[:, None]) & valid[None]),
                   -1)
    pg = pgen.astype(jnp.float32)
    return jnp.log(pg * gen + (1.0 - pg) * copy + fam.LOG_EPS)


def final_dist_at(fam, p, hp, ids, ext_ids, n, dec_inputs, t, act=SOUND):
    """The extended-vocabulary distribution [V + oov] for the token that
    follows dec_inputs[:t+1] (decode semantics), one article, K rows:
    dec_inputs [K, Td]: each row the family's own `next_dist`, else the
    pointer mixture."""
    if hasattr(fam, "next_dist"):
        return jax.vmap(lambda row: fam.next_dist(
            p, hp, ids, ext_ids, n, row, t, act))(dec_inputs)
    V, n_oov = int(hp["vocab_size"]), int(hp["max_oov_buckets"])
    enc = fam.encode(p, hp, ids, n, act)
    valid = jnp.arange(ids.shape[0]) < n

    def one(row):
        proj_in, W, b, att, pgen = fam.decode(p, hp, enc, row, True, act)
        scores = (proj_in[t] @ W + b).astype(jnp.float32)
        vocab = jax.nn.softmax(scores)
        pg = pgen[t].astype(jnp.float32)
        dist = jnp.zeros((V + n_oov,), jnp.float32).at[:V].set(pg * vocab)
        return dist.at[ext_ids].add(
            jnp.where(valid, (1.0 - pg) * att[t].astype(jnp.float32), 0.0))

    return jax.vmap(one)(dec_inputs)


# ------------------------------------------------------------------- loss

def batch_loss(fam, p, hp, arrays, act=SOUND):
    """The training loss of one batch (dict of [B, ...] arrays as the
    trainer's feed delivers them): per row, the masked mean of the
    negative log mixture probability of the target; then the mean over
    rows."""
    def row(ids, ext, n, dec, tgt, mask):
        lp = _token_logprobs(fam, p, hp, ids, ext, n, dec, tgt, False, act)
        return jnp.sum(-lp * mask) / jnp.sum(mask)

    losses = jax.vmap(row)(
        arrays["enc_batch"], arrays["enc_batch_extend_vocab"],
        arrays["enc_lens"], arrays["dec_batch"], arrays["target_batch"],
        arrays["dec_padding_mask"])
    return jnp.mean(losses)


def loss_and_grads(fam, p, hp, arrays, block: int, loss_grad=None):
    """Loss and gradients of one batch, computed in blocks of `block`
    rows so that the reference fits beside nothing else on the chip.
    `loss_grad(params, rows) -> (loss, grads)` stands in for the sound
    computation where a control wants another one."""
    B = int(arrays["enc_batch"].shape[0])
    assert B % block == 0, (B, block)
    fn = jax.jit(loss_grad or jax.value_and_grad(
        lambda q, a: batch_loss(fam, q, hp, a)))
    loss, grads = 0.0, None
    for i in range(0, B, block):
        part = {k: v[i:i + block] for k, v in arrays.items()}
        l, g = fn(p, part)
        loss = loss + l
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    k = B // block
    return loss / k, jax.tree_util.tree_map(lambda g: g / k, grads)


@jax.jit
def _adagrad(p, acc, grads, lr, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-30))
    grads = jax.tree_util.tree_map(lambda g: g * scale.astype(g.dtype), grads)
    acc = jax.tree_util.tree_map(lambda a, g: a + jnp.square(g), acc, grads)
    p = jax.tree_util.tree_map(
        lambda x, g, a: x - (lr * g * jax.lax.rsqrt(a)).astype(x.dtype),
        p, grads, acc)
    return p, acc, grads


def train_steps(fam, p0, hp, batches: Sequence[Dict[str, Any]], block: int,
                loss_grad=None):
    """Follow the trainer through len(batches) steps from p0: TF1
    Adagrad (accumulator starts at adagrad_init_acc, no epsilon) after a
    global-norm clip.  Returns (losses, per-leaf norms of the first
    clipped gradient, per-leaf norms of the parameters' change after the
    last step), leaves in jax.tree_util order."""
    acc = jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, float(hp["adagrad_init_acc"])), p0)
    p, losses, g1 = p0, [], None
    for arrays in batches:
        arrays = {k: jnp.asarray(v) for k, v in arrays.items()}
        loss, grads = loss_and_grads(fam, p, hp, arrays, block, loss_grad)
        p, acc, clipped = _adagrad(p, acc, grads, float(hp["lr"]),
                                   float(hp["max_grad_norm"]))
        losses.append(float(loss))
        if g1 is None:
            g1 = leaf_norms(clipped)
    return losses, g1, leaf_norms(tree_sub(p, p0))


@jax.jit
def tree_sub(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)


def leaf_norms(tree) -> np.ndarray:
    return np.asarray(jax.jit(lambda t: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
         for x in jax.tree_util.tree_leaves(t)]))(tree))


def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


# ---------------------------------------------------------------- serving

def score_inputs(hp, articles: Sequence[Tuple[np.ndarray, np.ndarray]],
                 outputs: Sequence[Sequence[int]]) -> Tuple[np.ndarray, ...]:
    """(ids, ext, lens, dec, tgt, mask), padded to the configuration's
    lengths: what `score_program` takes after the parameters."""
    Te, Td = int(hp["max_enc_steps"]), int(hp["max_dec_steps"])
    V = int(hp["vocab_size"])
    n_art = len(articles)
    ids = np.full((n_art, Te), PAD_ID, np.int32)
    ext = np.full((n_art, Te), PAD_ID, np.int32)
    lens = np.zeros((n_art,), np.int32)
    dec = np.full((n_art, Td), PAD_ID, np.int32)
    tgt = np.full((n_art, Td), PAD_ID, np.int32)
    mask = np.zeros((n_art, Td), np.float32)
    for i, ((a, e), out) in enumerate(zip(articles, outputs)):
        lens[i] = len(a)
        ids[i, :len(a)], ext[i, :len(a)] = a, e
        out = list(out)[:Td]
        inp = [START_ID] + [t if t < V else UNK_ID for t in out[:-1]]
        dec[i, :len(inp)], tgt[i, :len(out)] = inp, out
        mask[i, :len(out)] = 1.0
    return ids, ext, lens, dec, tgt, mask


def score_program(fam, hp, act=SOUND):
    """The jitted program behind `score_tokens`: (parameters as they are
    stored, *score_inputs) -> each row's sum of log probabilities, one
    article at a time."""
    @jax.jit
    def run(q, ids, ext, lens, dec, tgt, mask):
        def row(i, e, n, d, t, m):
            return jnp.sum(_token_logprobs(fam, q, hp, i, e, n, d, t, True,
                                           act) * m)
        return jax.lax.map(lambda xs: row(*xs),
                           (ids, ext, lens, dec, tgt, mask))

    return run


def score_tokens(fam, p, hp, articles: Sequence[Tuple[np.ndarray, np.ndarray]],
                 outputs: Sequence[Sequence[int]], act=SOUND) -> np.ndarray:
    """For each (article ids, extended ids) and its served output tokens
    (extended ids, STOP included where the search stopped): the sum of
    log mixture probabilities of the tokens under decode semantics."""
    return np.asarray(score_program(fam, hp, act)(
        p, *score_inputs(hp, articles, outputs)))


@functools.lru_cache(maxsize=None)
def _topk_fn(family_module: str, hp_json: str, act: str):
    """The 2K best continuations of K prefixes of one article."""
    fam, hp = importlib.import_module(family_module), json.loads(hp_json)

    @jax.jit
    def topk(q, ids, ext, n, rows, t):
        dist = final_dist_at(fam, q, hp, ids, ext, n, rows, t,
                             jnp.dtype(act))
        probs, toks = jax.lax.top_k(dist, 2 * int(hp["beam_size"]))
        return toks, jnp.log(probs + fam.LOG_EPS)

    return topk


def beam_search(fam, p, hp, art_ids: np.ndarray, ext_ids: np.ndarray,
                on_step=None, act=SOUND) -> Tuple[List[int], float]:
    """See et al.'s beam search (abisee beam_search.py) for one article,
    in plain Python over the reference's distributions.  Returns (the
    best hypothesis' generated tokens, its length-normalised log
    probability: total over len(tokens) + 1 for START).  `on_step(step,
    ranked, kept, results)` sees every pruning (a builder's look at where
    two searches part: tools/probe.py); it changes nothing."""
    K, V = int(hp["beam_size"]), int(hp["vocab_size"])
    Te, Td = int(hp["max_enc_steps"]), int(hp["max_dec_steps"])
    min_steps = int(hp["min_dec_steps"])
    ids = np.full((Te,), PAD_ID, np.int32)
    ext = np.full((Te,), PAD_ID, np.int32)
    ids[:len(art_ids)], ext[:len(art_ids)] = art_ids, ext_ids
    n = np.int32(len(art_ids))
    topk = _topk_fn(fam.__name__, json.dumps(hp, sort_keys=True),
                    jnp.dtype(act).name)

    hyps = [([], 0.0)] * K  # (generated tokens, total log prob)
    results: List[Tuple[List[int], float]] = []
    steps = 0

    def avg(h):
        return h[1] / (len(h[0]) + 1)

    while steps < Td and len(results) < K:
        rows = np.full((K, Td), PAD_ID, np.int32)
        for i, (toks, _) in enumerate(hyps):
            inp = [START_ID] + [t if t < V else UNK_ID for t in toks]
            rows[i, :len(inp)] = inp
        cand_toks, cand_lp = (np.asarray(x) for x in
                              topk(p, ids, ext, n, rows, steps))
        all_h = []
        for i in range(1 if steps == 0 else len(hyps)):
            for j in range(2 * K):
                all_h.append((hyps[i][0] + [int(cand_toks[i, j])],
                              hyps[i][1] + float(cand_lp[i, j])))
        hyps = []
        ranked = sorted(all_h, key=avg, reverse=True)
        for h in ranked:
            if h[0][-1] == STOP_ID:
                if steps >= min_steps:
                    results.append(h)
            else:
                hyps.append(h)
            if len(hyps) == K or len(results) == K:
                break
        if on_step is not None:
            on_step(steps, ranked, hyps, results)
        steps += 1
    if not results:
        results = hyps
    best = max(results, key=avg)
    return best[0], avg(best)
