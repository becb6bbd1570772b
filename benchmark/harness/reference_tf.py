"""Plain reference: the encoder-decoder copy-transformer (the widths of
OpenNMT-py's CNN/DM summarizer, Gehrmann et al. 2018) in straightforward
jax.numpy, one article at a time, no kernels, no KV cache, no batching.
Imports nothing of the program.

Layer equations (departures from OpenNMT-py are listed in
configs/tf_cnndm.json "assumed"): pre-LN residual blocks, learned
position tables, tied input/output embedding, tanh-approximate GELU; the
copy distribution is the LAST decoder layer's cross-attention averaged
over heads, and p_gen = sigmoid(W [h; cross_out] + b) with h the final
layer-normed state and cross_out that layer's cross-attention output.
Decoding and training use the same equations (`decode_mode` changes
nothing here), but the family takes log(p + 1e-10).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LOG_EPS = 1e-10


def _ln(p, x):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + 1e-6) * p["scale"]
            + p["bias"]).astype(x.dtype)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _mha(p, nh, q_in, kv_in, mask):
    """q_in [Tq, H], kv_in [Tk, H], mask [Tq, Tk] bool.  Returns
    (output [Tq, H], head-averaged probabilities [Tq, Tk])."""
    Tq, H = q_in.shape
    hd = H // nh
    q = (q_in @ p["wq"]).reshape(Tq, nh, hd)
    k = (kv_in @ p["wk"]).reshape(-1, nh, hd)
    v = (kv_in @ p["wv"]).reshape(-1, nh, hd)
    logits = jnp.einsum("qnd,knd->nqk", q, k).astype(jnp.float32) * hd ** -0.5
    logits = jnp.where(mask[None], logits, -1e30)
    probs = jax.nn.softmax(logits, -1)
    ctx = jnp.einsum("nqk,knd->qnd", probs.astype(v.dtype), v).reshape(Tq, H)
    return ctx @ p["wo"], jnp.mean(probs, 0)


def _ffn(p, x):
    return _gelu(x @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]


def encode(p, hp, ids, n):
    T = ids.shape[0]
    nh = int(hp["num_heads"])
    valid = jnp.arange(T) < n
    x = p["embedding"][ids] + p["pos_enc"][:T]
    mask = jnp.broadcast_to(valid[None, :], (T, T))
    for layer in p["encoder"]["layers"]:
        h = _ln(layer["ln1"], x)
        x = x + _mha(layer["self_attn"], nh, h, h, mask)[0]
        x = x + _ffn(layer["ffn"], _ln(layer["ln2"], x))
    return {"out": _ln(p["encoder"]["ln_out"], x), "valid": valid}


def decode(p, hp, enc, dec_inputs, decode_mode):
    """Teacher-forced decoder over dec_inputs [Td].  Returns (proj_in
    [Td, H], W [H, V], b [V], att [Td, T], p_gen [Td])."""
    del decode_mode
    nh = int(hp["num_heads"])
    Td = dec_inputs.shape[0]
    y = p["embedding"][dec_inputs] + p["pos_dec"][:Td]
    causal = jnp.tril(jnp.ones((Td, Td), bool))
    cross_mask = jnp.broadcast_to(enc["valid"][None, :],
                                  (Td, enc["valid"].shape[0]))
    for layer in p["decoder"]["layers"]:
        h = _ln(layer["ln1"], y)
        y = y + _mha(layer["self_attn"], nh, h, h, causal)[0]
        cross, att = _mha(layer["cross_attn"], nh, _ln(layer["ln_cross"], y),
                          enc["out"], cross_mask)
        y = y + cross
        y = y + _ffn(layer["ffn"], _ln(layer["ln2"], y))
    h = _ln(p["decoder"]["ln_out"], y)
    pgen = jax.nn.sigmoid(
        jnp.concatenate([h, cross], -1) @ p["pgen_linear"]["kernel"]
        + p["pgen_linear"]["bias"])[:, 0]
    return h, p["embedding"].T, p["out_bias"], att, pgen
