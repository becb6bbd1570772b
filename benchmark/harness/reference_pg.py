"""Plain reference: the pointer-generator of See et al. 2017 in
straightforward jax.numpy, one article at a time, no kernels, no cache,
no batching.  Imports nothing of the program.

Follows the paper and abisee/pointer-generator (model.py,
attention_decoder.py): TF1 LSTMCell gate order [i, j, f, o] with forget
bias 1, a bidirectional encoder whose backward direction runs over the
valid prefix only, reduced initial state, Bahdanau attention with the
padding renormalised away, coverage off.  `decode_mode` selects the
reference's initial_state_attention=True quirk: at decode time the first
step's context is the attention at the initial state, in training it is
zero.

Every function computes in the dtype of the parameters it is handed
(float32; the low-precision control rounds the parameters it hands in),
and the caller sets the matmul precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LOG_EPS = 0.0  # the family's log(p + eps) at decode time and in the loss


def _lstm(cell, x, c, h):
    z = jnp.concatenate([x, h], -1) @ cell["kernel"] + cell["bias"]
    i, j, f, o = jnp.split(z, 4, -1)
    c2 = c * jax.nn.sigmoid(f + 1.0) + jax.nn.sigmoid(i) * jnp.tanh(j)
    return c2, jnp.tanh(c2) * jax.nn.sigmoid(o)


def encode(p, hp, ids, n):
    """ids: [T] fixed-vocabulary ids (padding past n is ignored).
    Returns the encoder view the decoder needs."""
    del hp
    emb = p["embedding"][ids]
    T = ids.shape[0]
    H = p["reduce"]["w_reduce_c"].shape[1]
    valid = jnp.arange(T) < n
    zero = jnp.zeros((H,), emb.dtype)

    def run(cell, reverse):
        def step(carry, xs):
            x, ok = xs
            c, h = carry
            c2, h2 = _lstm(cell, x, c, h)
            return ((jnp.where(ok, c2, c), jnp.where(ok, h2, h)),
                    jnp.where(ok, h2, 0))

        (c, h), outs = jax.lax.scan(step, (zero, zero), (emb, valid),
                                    reverse=reverse)
        return outs, c, h

    fw, fc, fh = run(p["encoder"]["fw"], False)
    bw, bc, bh = run(p["encoder"]["bw"], True)
    states = jnp.concatenate([fw, bw], -1)  # [T, 2H]
    r = p["reduce"]
    c0 = jax.nn.relu(jnp.concatenate([fc, bc]) @ r["w_reduce_c"]
                     + r["bias_reduce_c"])
    h0 = jax.nn.relu(jnp.concatenate([fh, bh]) @ r["w_reduce_h"]
                     + r["bias_reduce_h"])
    feats = states @ p["decoder"]["attention"]["W_h"]
    return {"states": states, "feats": feats, "valid": valid,
            "c0": c0, "h0": h0}


def _attend(a, enc, c, h):
    dec = jnp.concatenate([c, h]) @ a["linear_kernel"] + a["linear_bias"]
    e = jnp.sum(a["v"] * jnp.tanh(enc["feats"] + dec), -1).astype(jnp.float32)
    e = jnp.where(enc["valid"], e, -jnp.inf)
    att = jax.nn.softmax(e).astype(enc["states"].dtype)
    return att @ enc["states"], att


def decode(p, hp, enc, dec_inputs, decode_mode):
    """Teacher-forced decoder over dec_inputs [Td] (fixed-vocabulary ids).
    Returns (proj_in [Td, H], W [H, V], b [V], att [Td, T], p_gen [Td]):
    vocabulary scores are proj_in @ W + b."""
    del hp
    d = p["decoder"]
    c, h = enc["c0"], enc["h0"]
    if decode_mode:
        ctx, _ = _attend(d["attention"], enc, c, h)
    else:
        ctx = jnp.zeros((enc["states"].shape[-1],), c.dtype)

    def step(carry, tok):
        c, h, ctx = carry
        x = (jnp.concatenate([p["embedding"][tok], ctx])
             @ d["input_linear"]["kernel"] + d["input_linear"]["bias"])
        c2, h2 = _lstm(d["cell"], x, c, h)
        ctx2, att = _attend(d["attention"], enc, c2, h2)
        pgen = jax.nn.sigmoid(
            jnp.concatenate([ctx2, c2, h2, x]) @ d["pgen_linear"]["kernel"]
            + d["pgen_linear"]["bias"])[0]
        out = (jnp.concatenate([h2, ctx2]) @ d["output_linear"]["kernel"]
               + d["output_linear"]["bias"])
        return (c2, h2, ctx2), (out, att, pgen)

    _, (outs, atts, pgens) = jax.lax.scan(step, (c, h, ctx), dec_inputs)
    return (outs, p["output_projection"]["w"], p["output_projection"]["v"],
            atts, pgens)
