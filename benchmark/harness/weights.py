"""Seed-made weights for both model families, built on the device in ONE
jitted call, in the type they are served and trained in (float32).

The benchmark owns this: the program's own `init_params` is not used, so
the reference and the program both receive weights the program has not
made.  The tree layout is the program's parameter layout (the names a
checkpoint uses), because that is the interface the weights cross.

Init (configs/<config>.json "init"): matrices normal(0, gain/sqrt(fan_in)),
embeddings and position tables normal(0, embedding_std), layer-norm scale
1, biases 0.

STOP: random weights give STOP a log probability near -11 that hardly
moves from step to step, so beam search never ends (PR 22), and a plain
bias on STOP's logit ends every summary at min_dec_steps + 1 tokens or
never.  A trained summarizer decides the length of a summary from the
article; seed-made weights cannot learn that, so `init.summary_clock`
WIRES it (pointer-generator only), with a few units of the model's own
LSTMs and no change to the model:
  * word id i carries a length code, L(i) = min_tokens + (i - 4) mod
    codes, as the value of ONE embedding dimension (the last);
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
the summary length the mix asks for, so every seed serves the same
multiset of summary lengths in another order.  Everything else in the
tree stays random, and both the program and the plain reference get the
same tree: neither knows of the clock.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

STOP_ID = 3

Spec = Tuple[Tuple[int, ...], str]  # (shape, kind)


def _attn(H: int) -> Dict[str, Spec]:
    return {k: ((H, H), "matrix") for k in ("wq", "wk", "wv", "wo")}


def _ln(H: int) -> Dict[str, Spec]:
    return {"scale": ((H,), "ones"), "bias": ((H,), "zeros")}


def _ffn(H: int, F: int) -> Dict[str, Spec]:
    return {"w1": ((H, F), "matrix"), "b1": ((F,), "zeros"),
            "w2": ((F, H), "matrix"), "b2": ((H,), "zeros")}


def param_specs(hp: Dict[str, Any]) -> Dict[str, Any]:
    """{leaf path: (shape, init kind)} as a nested dict in the program's
    parameter layout, from a config file's "hparams"."""
    V = int(hp["vocab_size"])
    H = int(hp["hidden_dim"])
    if hp["model_family"] == "pointer_generator":
        E, D = int(hp["emb_dim"]), 2 * H

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
    if hp["model_family"] == "transformer":
        F = int(hp.get("ffn_dim") or 4 * H)
        Te, Td = int(hp["max_enc_steps"]), int(hp["max_dec_steps"])
        enc = [{"ln1": _ln(H), "self_attn": _attn(H), "ln2": _ln(H),
                "ffn": _ffn(H, F)} for _ in range(int(hp["enc_layers"]))]
        dec = [{"ln1": _ln(H), "self_attn": _attn(H), "ln_cross": _ln(H),
                "cross_attn": _attn(H), "ln2": _ln(H), "ffn": _ffn(H, F)}
               for _ in range(int(hp["dec_layers"]))]
        return {
            "embedding": ((V, H), "tied_embedding"),
            "pos_enc": ((Te, H), "embedding"),
            "pos_dec": ((Td + 1, H), "embedding"),
            "encoder": {"layers": enc, "ln_out": _ln(H)},
            "decoder": {"layers": dec, "ln_out": _ln(H)},
            "pgen_linear": {"kernel": ((2 * H, 1), "matrix"),
                            "bias": ((1,), "zeros")},
            "out_bias": ((V,), "vocab_bias"),
        }
    raise ValueError(f"no weight layout for family {hp['model_family']!r}")


def _is_spec(x) -> bool:
    return (isinstance(x, tuple) and len(x) == 2
            and isinstance(x[0], tuple) and isinstance(x[1], str))


def n_params(specs) -> int:
    leaves = jax.tree_util.tree_leaves(specs, is_leaf=_is_spec)
    return int(sum(int(np.prod(s[0])) for s in leaves))


def length_code(clock: Dict[str, Any], ids) -> Any:
    """The summary length (tokens, STOP included) that word id `ids`
    codes for as an article's first word."""
    return int(clock["min_tokens"]) + (ids - 4) % int(clock["codes"])


def _wire_summary_clock(p, hp: Dict[str, Any], clock: Dict[str, Any]):
    """See the module's docstring.  Gate order of a cell's kernel columns
    is TF1's [i | j | f | o], each H wide, rows [input | recurrent h]."""
    if hp["model_family"] != "pointer_generator":
        raise ValueError("summary_clock is wired for the pointer-generator "
                         "only")
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


def make_params(cfg: Dict[str, Any], seed: int):
    """The parameter tree for `cfg` (a loaded config file) from `seed`,
    made on the default device by one jitted function."""
    specs = param_specs(cfg["hparams"])
    init = cfg["init"]
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=_is_spec)

    def build(key):
        out = []
        for i, (shape, kind) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            if kind == "zeros":
                x = jnp.zeros(shape, jnp.float32)
            elif kind == "ones":
                x = jnp.ones(shape, jnp.float32)
            elif kind == "vocab_bias":
                x = jnp.zeros(shape, jnp.float32).at[STOP_ID].set(
                    float(init.get("stop_bias", 0.0)))
            elif kind in ("embedding", "tied_embedding"):
                x = float(init["embedding_std"]) * jax.random.normal(
                    k, shape, jnp.float32)
            elif kind == "vector":
                x = float(init.get("vector_std", 0.05)) * jax.random.normal(
                    k, shape, jnp.float32)
            else:  # matrix / lstm / vocab: normal(0, gain / sqrt(fan_in))
                gain = float(init.get(f"{kind}_gain",
                                      init.get("matrix_gain", 1.0)))
                x = (gain / np.sqrt(shape[0])) * jax.random.normal(
                    k, shape, jnp.float32)
            out.append(x)
        tree = jax.tree_util.tree_unflatten(treedef, out)
        if init.get("summary_clock"):
            tree = _wire_summary_clock(tree, cfg["hparams"],
                                       init["summary_clock"])
        return tree

    # --seed may exceed 2**31: fold it into the key in two 31-bit halves
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return jax.jit(build)(key)
