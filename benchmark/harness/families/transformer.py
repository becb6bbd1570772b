"""The encoder-decoder copy-transformer (the widths of OpenNMT-py's CNN/DM
summarizer, Gehrmann et al. 2018): its parameter layout, its plain
reference and its counts.  Imports nothing of the program.  It offers no
summary clock (its decoder has no recurrent state; PERF.md section 7).

The reference is straightforward jax.numpy, one article at a time, no
kernels, no KV cache, no batching.  It computes in `act`, the
activations' type its caller states (families/__init__.py has the rule):
a leaf is cast to it where it is read, whatever type the tree stores; the
layer norm's statistics and the softmax's inputs are float32 in either.

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

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = 4

LOG_EPS = 1e-10


def _ln(p, x, act):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + 1e-6) * p["scale"].astype(act)
            + p["bias"].astype(act)).astype(act)


def _gelu(x):
    # a Python float, which JAX types weakly: a NumPy scalar here would
    # widen the bfloat16 control's residual stream to float32 from the
    # first feed-forward block on (PERF.md section 6, PR 36)
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _mha(p, nh, q_in, kv_in, mask, act):
    """q_in [Tq, H], kv_in [Tk, H], mask [Tq, Tk] bool.  Returns
    (output [Tq, H], head-averaged probabilities [Tq, Tk])."""
    Tq, H = q_in.shape
    hd = H // nh
    q = (q_in @ p["wq"].astype(act)).reshape(Tq, nh, hd)
    k = (kv_in @ p["wk"].astype(act)).reshape(-1, nh, hd)
    v = (kv_in @ p["wv"].astype(act)).reshape(-1, nh, hd)
    logits = jnp.einsum("qnd,knd->nqk", q, k).astype(jnp.float32) * hd ** -0.5
    logits = jnp.where(mask[None], logits, -1e30)
    probs = jax.nn.softmax(logits, -1)
    ctx = jnp.einsum("nqk,knd->qnd", probs.astype(act), v).reshape(Tq, H)
    return ctx @ p["wo"].astype(act), jnp.mean(probs, 0)


def _ffn(p, x, act):
    return (_gelu(x @ p["w1"].astype(act) + p["b1"].astype(act))
            @ p["w2"].astype(act) + p["b2"].astype(act))


def encode(p, hp, ids, n, act):
    T = ids.shape[0]
    nh = int(hp["num_heads"])
    valid = jnp.arange(T) < n
    x = p["embedding"][ids].astype(act) + p["pos_enc"][:T].astype(act)
    mask = jnp.broadcast_to(valid[None, :], (T, T))
    for layer in p["encoder"]["layers"]:
        h = _ln(layer["ln1"], x, act)
        x = x + _mha(layer["self_attn"], nh, h, h, mask, act)[0]
        x = x + _ffn(layer["ffn"], _ln(layer["ln2"], x, act), act)
    return {"out": _ln(p["encoder"]["ln_out"], x, act), "valid": valid}


def decode(p, hp, enc, dec_inputs, decode_mode, act):
    """Teacher-forced decoder over dec_inputs [Td].  Returns (proj_in
    [Td, H], W [H, V], b [V], att [Td, T], p_gen [Td])."""
    del decode_mode
    nh = int(hp["num_heads"])
    Td = dec_inputs.shape[0]
    y = (p["embedding"][dec_inputs].astype(act)
         + p["pos_dec"][:Td].astype(act))
    causal = jnp.tril(jnp.ones((Td, Td), bool))
    cross_mask = jnp.broadcast_to(enc["valid"][None, :],
                                  (Td, enc["valid"].shape[0]))
    for layer in p["decoder"]["layers"]:
        h = _ln(layer["ln1"], y, act)
        y = y + _mha(layer["self_attn"], nh, h, h, causal, act)[0]
        cross, att = _mha(layer["cross_attn"], nh,
                          _ln(layer["ln_cross"], y, act), enc["out"],
                          cross_mask, act)
        y = y + cross
        y = y + _ffn(layer["ffn"], _ln(layer["ln2"], y, act), act)
    h = _ln(p["decoder"]["ln_out"], y, act)
    pgen = jax.nn.sigmoid(
        jnp.concatenate([h, cross], -1)
        @ p["pgen_linear"]["kernel"].astype(act)
        + p["pgen_linear"]["bias"].astype(act))[:, 0]
    return (h, p["embedding"].T.astype(act), p["out_bias"].astype(act), att,
            pgen)


# ----------------------------------------------------------------- layout

def _attn_specs(H: int):
    return {k: ((H, H), "matrix") for k in ("wq", "wk", "wv", "wo")}


def _ln_specs(H: int):
    return {"scale": ((H,), "ones"), "bias": ((H,), "zeros")}


def _ffn_specs(H: int, F: int):
    return {"w1": ((H, F), "matrix"), "b1": ((F,), "zeros"),
            "w2": ((F, H), "matrix"), "b2": ((H,), "zeros")}


def _ffn_dim(hp) -> int:
    return int(hp.get("ffn_dim") or 4 * int(hp["hidden_dim"]))


def param_specs(hp: Dict[str, Any]) -> Dict[str, Any]:
    """{leaf path: (shape, init kind)} as a nested dict in the program's
    parameter layout, from a config file's "hparams"."""
    V, H, F = int(hp["vocab_size"]), int(hp["hidden_dim"]), _ffn_dim(hp)
    Te, Td = int(hp["max_enc_steps"]), int(hp["max_dec_steps"])
    enc = [{"ln1": _ln_specs(H), "self_attn": _attn_specs(H),
            "ln2": _ln_specs(H), "ffn": _ffn_specs(H, F)}
           for _ in range(int(hp["enc_layers"]))]
    dec = [{"ln1": _ln_specs(H), "self_attn": _attn_specs(H),
            "ln_cross": _ln_specs(H), "cross_attn": _attn_specs(H),
            "ln2": _ln_specs(H), "ffn": _ffn_specs(H, F)}
           for _ in range(int(hp["dec_layers"]))]
    return {
        "embedding": ((V, H), "tied_embedding"),
        "pos_enc": ((Te, H), "embedding"),
        "pos_dec": ((Td + 1, H), "embedding"),
        "encoder": {"layers": enc, "ln_out": _ln_specs(H)},
        "decoder": {"layers": dec, "ln_out": _ln_specs(H)},
        "pgen_linear": {"kernel": ((2 * H, 1), "matrix"),
                        "bias": ((1,), "zeros")},
        "out_bias": ((V,), "vocab_bias"),
    }


# ----------------------------------------------------------------- counts

def _layer_macs(hp, Te, Td):
    H, F = int(hp["hidden_dim"]), _ffn_dim(hp)
    enc_layer = 4 * Te * H * H + 2 * Te * Te * H + 2 * Te * H * F
    dec_layer = (4 * Td * H * H + 2 * Td * Td * H + 2 * Td * H * H
                 + 2 * Te * H * H + 2 * Td * Te * H + 2 * Td * H * F)
    return enc_layer, dec_layer


def forward_macs_per_row(hp, Te, Td) -> float:
    H, V = int(hp["hidden_dim"]), int(hp["vocab_size"])
    enc_layer, dec_layer = _layer_macs(hp, Te, Td)
    return (int(hp["enc_layers"]) * enc_layer
            + int(hp["dec_layers"]) * dec_layer + Td * H * V)


def beam_state_bytes(hp) -> int:
    """One resident's per-hypothesis decode state that a step reads and
    writes: the self-attention K/V cache."""
    return (int(hp["beam_size"]) * int(hp["dec_layers"])
            * (int(hp["max_dec_steps"]) + 1) * int(hp["hidden_dim"])
            * 2 * F32)


def enc_view_bytes(hp, Te) -> float:
    """One resident's encoder view that every decode step reads: the
    per-layer cross-attention K/V."""
    return int(hp["dec_layers"]) * Te * int(hp["hidden_dim"]) * 2 * F32


def decode_step_macs_per_hyp(hp, Te, t) -> float:
    """One decode step for one hypothesis at decode position t over an
    article of Te tokens."""
    H, V, F = int(hp["hidden_dim"]), int(hp["vocab_size"]), _ffn_dim(hp)
    layer = (4 * H * H + 2 * (t + 1) * H + 2 * H * H + 2 * Te * H
             + 2 * H * F)
    return int(hp["dec_layers"]) * layer + H * V + 2 * H


def prefill_macs_and_weights(hp, Te):
    """(MACs, weight elements read) of one prefill call for one article
    of Te tokens: the encoder, and the cross-attention K/V it leaves
    behind."""
    H = int(hp["hidden_dim"])
    enc_layer, _ = _layer_macs(hp, Te, 0)
    macs = (int(hp["enc_layers"]) * enc_layer
            + int(hp["dec_layers"]) * 2 * Te * H * H)
    w = (int(hp["enc_layers"]) * (4 * H * H + 2 * H * _ffn_dim(hp))
         + int(hp["dec_layers"]) * 2 * H * H)
    return macs, w
