"""The pointer-generator of See et al. 2017: its parameter layout, its
plain reference, its counts, and the summary clock wired into its
seed-made weights.  Imports nothing of the program.

THE REFERENCE, in straightforward jax.numpy, one article at a time, no
kernels, no cache, no batching.  Follows the paper and
abisee/pointer-generator (model.py, attention_decoder.py): TF1 LSTMCell
gate order [i, j, f, o] with forget bias 1, a bidirectional encoder whose
backward direction runs over the valid prefix only, reduced initial
state, Bahdanau attention with the padding renormalised away, coverage
off.  `decode_mode` selects the reference's initial_state_attention=True
quirk: at decode time the first step's context is the attention at the
initial state, in training it is zero.  Every function computes in `act`,
the activations' type its caller states (families/__init__.py has the
rule): a leaf is cast to it where it is read, whatever type the tree
stores, and the caller sets the matmul precision.

THE SUMMARY CLOCK.  Random weights give STOP a log probability near -11
that hardly moves from step to step, so beam search never ends (PR 22),
and a plain bias on STOP's logit ends every summary at min_dec_steps + 1
tokens or never.  A trained summarizer decides the length of a summary
from the article; seed-made weights cannot learn that, so
`init.summary_clock` WIRES it (`wire`), with a few units of the model's
own LSTMs and no change to the model:
  * word id i carries a length code, L(i) = min_tokens + (i - 4) mod
    codes (`length_code`), as the value of ONE embedding dimension (the
    last);
  * ONE unit of the backward encoder LSTM forgets everything and latches
    that dimension, so its final cell state is the code of the article's
    FIRST word (the backward pass reads it last);
  * `units` units of the decoder LSTM are a clock: their initial cell
    state is c_star - step * L (+ a small phase each) through the reduce
    layer, they ignore every input and add `step` a decode step;
  * their outputs reach STOP's logit alone, with weight gain / units:
    STOP's logit is gain * tanh(clock) + stop_bias, which crosses the
    best word's logit at decode step L.
The traffic generator (traffic.py) chooses each article's first word by
the summary length the mix asks for (`word_for_length`), so every seed
serves the same multiset of summary lengths in another order.
Everything else in the tree stays random, and both the program and the
plain reference get the same tree: neither knows of the clock.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

STOP_ID = 3
F32 = 4

LOG_EPS = 0.0  # the family's log(p + eps) at decode time and in the loss


def _lstm(cell, x, c, h, act):
    z = (jnp.concatenate([x, h], -1) @ cell["kernel"].astype(act)
         + cell["bias"].astype(act))
    i, j, f, o = jnp.split(z, 4, -1)
    c2 = c * jax.nn.sigmoid(f + 1.0) + jax.nn.sigmoid(i) * jnp.tanh(j)
    return c2, jnp.tanh(c2) * jax.nn.sigmoid(o)


def encode(p, hp, ids, n, act):
    """ids: [T] fixed-vocabulary ids (padding past n is ignored).
    Returns the encoder view the decoder needs."""
    del hp
    emb = p["embedding"][ids].astype(act)
    T = ids.shape[0]
    H = p["reduce"]["w_reduce_c"].shape[1]
    valid = jnp.arange(T) < n
    zero = jnp.zeros((H,), act)

    def run(cell, reverse):
        def step(carry, xs):
            x, ok = xs
            c, h = carry
            c2, h2 = _lstm(cell, x, c, h, act)
            return ((jnp.where(ok, c2, c), jnp.where(ok, h2, h)),
                    jnp.where(ok, h2, 0))

        (c, h), outs = jax.lax.scan(step, (zero, zero), (emb, valid),
                                    reverse=reverse)
        return outs, c, h

    fw, fc, fh = run(p["encoder"]["fw"], False)
    bw, bc, bh = run(p["encoder"]["bw"], True)
    states = jnp.concatenate([fw, bw], -1)  # [T, 2H]
    r = {k: v.astype(act) for k, v in p["reduce"].items()}
    c0 = jax.nn.relu(jnp.concatenate([fc, bc]) @ r["w_reduce_c"]
                     + r["bias_reduce_c"])
    h0 = jax.nn.relu(jnp.concatenate([fh, bh]) @ r["w_reduce_h"]
                     + r["bias_reduce_h"])
    feats = states @ p["decoder"]["attention"]["W_h"].astype(act)
    return {"states": states, "feats": feats, "valid": valid,
            "c0": c0, "h0": h0}


def _attend(a, enc, c, h, act):
    dec = (jnp.concatenate([c, h]) @ a["linear_kernel"].astype(act)
           + a["linear_bias"].astype(act))
    e = jnp.sum(a["v"].astype(act) * jnp.tanh(enc["feats"] + dec),
                -1).astype(jnp.float32)
    e = jnp.where(enc["valid"], e, -jnp.inf)
    att = jax.nn.softmax(e).astype(act)
    return att @ enc["states"], att


def _linear(lin, x, act):
    return x @ lin["kernel"].astype(act) + lin["bias"].astype(act)


def decode(p, hp, enc, dec_inputs, decode_mode, act):
    """Teacher-forced decoder over dec_inputs [Td] (fixed-vocabulary ids).
    Returns (proj_in [Td, H], W [H, V], b [V], att [Td, T], p_gen [Td]):
    vocabulary scores are proj_in @ W + b."""
    del hp
    d = p["decoder"]
    c, h = enc["c0"], enc["h0"]
    if decode_mode:
        ctx, _ = _attend(d["attention"], enc, c, h, act)
    else:
        ctx = jnp.zeros((enc["states"].shape[-1],), act)

    def step(carry, tok):
        c, h, ctx = carry
        x = _linear(d["input_linear"], jnp.concatenate(
            [p["embedding"][tok].astype(act), ctx]), act)
        c2, h2 = _lstm(d["cell"], x, c, h, act)
        ctx2, att = _attend(d["attention"], enc, c2, h2, act)
        pgen = jax.nn.sigmoid(_linear(
            d["pgen_linear"], jnp.concatenate([ctx2, c2, h2, x]), act))[0]
        out = _linear(d["output_linear"], jnp.concatenate([h2, ctx2]), act)
        return (c2, h2, ctx2), (out, att, pgen)

    _, (outs, atts, pgens) = jax.lax.scan(step, (c, h, ctx), dec_inputs)
    head = p["output_projection"]
    return outs, head["w"].astype(act), head["v"].astype(act), atts, pgens


# ----------------------------------------------------------------- layout

def param_specs(hp: Dict[str, Any]) -> Dict[str, Any]:
    """{leaf path: (shape, init kind)} as a nested dict in the program's
    parameter layout, from a config file's "hparams"."""
    V, H, E = int(hp["vocab_size"]), int(hp["hidden_dim"]), int(hp["emb_dim"])
    D = 2 * H

    def cell():
        return {"kernel": ((E + H, 4 * H), "lstm"),
                "bias": ((4 * H,), "zeros")}

    return {
        "embedding": ((V, E), "embedding"),
        "encoder": {"fw": cell(), "bw": cell()},
        "reduce": {"w_reduce_c": ((D, H), "matrix"),
                   "w_reduce_h": ((D, H), "matrix"),
                   "bias_reduce_c": ((H,), "zeros"),
                   "bias_reduce_h": ((H,), "zeros")},
        "decoder": {
            "cell": cell(),
            "attention": {"W_h": ((D, D), "matrix"),
                          "v": ((D,), "vector"),
                          "w_c": ((D,), "vector"),
                          "linear_kernel": ((D, D), "matrix"),
                          "linear_bias": ((D,), "zeros")},
            "input_linear": {"kernel": ((E + D, E), "matrix"),
                             "bias": ((E,), "zeros")},
            "pgen_linear": {"kernel": ((D + H + H + E, 1), "matrix"),
                            "bias": ((1,), "zeros")},
            "output_linear": {"kernel": ((H + D, H), "matrix"),
                              "bias": ((H,), "zeros")},
        },
        "output_projection": {"w": ((H, V), "vocab"),
                              "v": ((V,), "vocab_bias")},
    }


# ------------------------------------------------------ the summary clock

# The clock at the benchmark tests' middle size (tests/tiny.py MID): what
# a test lays over the `init` of a configuration that asks for a clock.
# It codes the lengths 5..24, and test_control.py holds every summary to
# within 3 tokens of the length coded.
MID_CLOCK = {"stop_bias": -12.6,
             "summary_clock": {"units": 4, "gain": 24.0, "step": 0.03,
                               "phase": 0.002, "c_star": 1.0, "codes": 20,
                               "min_tokens": 5}}

def length_code(clock: Dict[str, Any], ids) -> Any:
    """The summary length (tokens, STOP included) that word id `ids`
    codes for as an article's first word."""
    return int(clock["min_tokens"]) + (ids - 4) % int(clock["codes"])


def word_for_length(clock: Dict[str, Any], length: int, rank: int,
                    n_words: int) -> int:
    """The word rank (id - 4) that codes for a summary of `length`
    tokens, in the block of `codes` ranks that holds `rank`: the inverse
    of `length_code` inside one Zipf neighbourhood."""
    codes, lo = int(clock["codes"]), int(clock["min_tokens"])
    if not lo <= length < lo + codes:
        raise ValueError(f"no code for a summary of {length}")
    rank = rank - rank % codes + int(length) - lo
    return rank - codes if rank >= n_words else rank


def wire(p, hp: Dict[str, Any], init: Dict[str, Any]):
    """See the module's docstring.  Gate order of a cell's kernel columns
    is TF1's [i | j | f | o], each H wide, rows [input | recurrent h]."""
    clock = init["summary_clock"]
    V, H, E = int(hp["vocab_size"]), int(hp["hidden_dim"]), int(hp["emb_dim"])
    n = int(clock["units"])
    if H < n + 2 or n < 1:
        raise ValueError(f"{n} clock units do not fit hidden_dim {H}")
    step, gain = float(clock["step"]), float(clock["gain"])
    c_star = float(clock["c_star"])
    codes, lo = int(clock["codes"]), int(clock["min_tokens"])
    mid = lo + (codes - 1) / 2.0
    span = float(clock.get("latch_span", 0.25))  # |latched value| at most
    half = (codes - 1) / 2.0
    open_, shut = 12.0, -12.0  # gate biases: sigmoid -> 1 and -> 0
    e, v, u = E - 1, H - 1, jnp.arange(n)  # code dim, latch unit, clock

    def gate(k, unit):  # column of gate k (0 i, 1 j, 2 f, 3 o) of a unit
        return k * H + unit

    # 1. the code: emb[i, e] = atanh(y) / latch_gain with y = span *
    # (mid - L(i)) / half, so that the latch holds y itself
    ids = jnp.arange(V)
    L = length_code(clock, ids)
    y = span * (mid - L) / half
    latch_gain = 0.5
    p["embedding"] = p["embedding"].at[:, e].set(
        jnp.arctanh(y) / latch_gain)
    # 2. the latch: backward encoder unit v; c = sigmoid(i) * tanh(j)
    bw = p["encoder"]["bw"]
    k, b = bw["kernel"], bw["bias"]
    for g in range(4):
        k = k.at[:, gate(g, v)].set(0.0)
    k = k.at[e, gate(1, v)].set(latch_gain)
    b = b.at[gate(0, v)].set(open_).at[gate(2, v)].set(shut - 1.0)
    p["encoder"]["bw"] = {"kernel": k, "bias": b}
    # 3. the clock's start: c0[u] = relu(y * w + c_star - step * mid +
    # phase[u]) with w = step * half / span, i.e. c_star - step * L
    r = p["reduce"]
    w = r["w_reduce_c"].at[:, u].set(0.0).at[H + v, u].set(
        step * half / span)
    phase = float(clock.get("phase", 0.0)) * jnp.arange(n)
    r["w_reduce_c"] = w
    r["bias_reduce_c"] = r["bias_reduce_c"].at[u].set(
        c_star - step * mid + phase)
    # 4. the clock: decoder units u ignore every input, keep their cell
    # state and add `step` a decode step; nothing else reads them
    d = p["decoder"]
    k, b = d["cell"]["kernel"], d["cell"]["bias"]
    for g in range(4):
        k = k.at[:, gate(g, u)].set(0.0)
    k = k.at[E + u, :].set(0.0)
    b = (b.at[gate(0, u)].set(open_).at[gate(1, u)].set(float(np.arctanh(step)))
         .at[gate(2, u)].set(open_ - 1.0).at[gate(3, u)].set(open_))
    d["cell"] = {"kernel": k, "bias": b}
    a = d["attention"]  # its query is [c, h] @ linear_kernel
    a["linear_kernel"] = a["linear_kernel"].at[u, :].set(0.0).at[
        H + u, :].set(0.0)
    D = 2 * H  # p_gen reads [context (D), c, h, x]
    d["pgen_linear"]["kernel"] = d["pgen_linear"]["kernel"].at[
        D + u, :].set(0.0).at[D + H + u, :].set(0.0)
    # 5. to STOP's logit alone: output unit u is the clock's h, and row u
    # of the vocabulary projection holds gain / n at STOP and 0 elsewhere
    o = d["output_linear"]["kernel"]  # rows [h (H), context (D)]
    o = o.at[u, :].set(0.0).at[:, u].set(0.0).at[u, u].set(1.0)
    d["output_linear"]["kernel"] = o
    d["output_linear"]["bias"] = d["output_linear"]["bias"].at[u].set(0.0)
    W = p["output_projection"]["w"]
    W = W.at[:, STOP_ID].set(0.0).at[u, :].set(0.0).at[u, STOP_ID].set(
        gain / n)
    p["output_projection"]["w"] = W
    return p


# ----------------------------------------------------------------- counts

def forward_macs_per_row(hp, Te, Td) -> float:
    H, V, E = int(hp["hidden_dim"]), int(hp["vocab_size"]), int(hp["emb_dim"])
    D = 2 * H
    enc_lstm = 2 * Te * (E + H) * 4 * H
    reduce_states = 2 * D * H
    enc_feats = Te * D * D
    dec_per_step = ((E + D) * E + (E + H) * 4 * H + D * D + Te * D + Te * D
                    + (2 * D + E) + (H + D) * H + H * V)
    return enc_lstm + reduce_states + enc_feats + Td * dec_per_step


def beam_state_bytes(hp) -> int:
    """One resident's per-hypothesis decode state that a step reads and
    writes: the LSTM (c, h)."""
    return int(hp["beam_size"]) * 2 * int(hp["hidden_dim"]) * F32


def enc_view_bytes(hp, Te) -> float:
    """One resident's encoder view that every decode step reads: encoder
    states and features."""
    return Te * 2 * int(hp["hidden_dim"]) * 2 * F32


def decode_step_macs_per_hyp(hp, Te, t) -> float:
    """One decode step for one hypothesis at decode position t over an
    article of Te tokens."""
    del t
    H, V, E = int(hp["hidden_dim"]), int(hp["vocab_size"]), int(hp["emb_dim"])
    D = 2 * H
    # decode mode attends twice a step (the previous context is rebuilt)
    return ((E + D) * E + (E + H) * 4 * H + 2 * (D * D + 2 * Te * D)
            + (2 * D + E) + (H + D) * H + H * V)


def prefill_macs_and_weights(hp, Te):
    """(MACs, weight elements read) of one prefill call for one article
    of Te tokens: the encoder, and the encoder view it leaves behind."""
    H, E = int(hp["hidden_dim"]), int(hp["emb_dim"])
    D = 2 * H
    macs = 2 * Te * (E + H) * 4 * H + 2 * D * H + Te * D * D
    return macs, 2 * (E + H) * 4 * H + 2 * D * H + D * D


def count_topk_rows(hp, dep, ctx) -> Dict[str, float]:
    """One call of the slot step: ONE read a decode step of the occupied
    residents' [beam x (vocab + oov buckets)] float32 rows, the least a
    selection of the beam's candidates must move (occupied residents
    only, as `counts.slot_chunk` is given them).  No FLOPs: the bound is bandwidth."""
    from harness import readers

    rows = readers.occupied_slots(ctx) * int(hp["beam_size"])
    width = int(hp["vocab_size"]) + int(hp["max_oov_buckets"])
    return {"flops": 0.0,
            "bytes": float(rows * width * F32 * int(dep["chunk"]))}
