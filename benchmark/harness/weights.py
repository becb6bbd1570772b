"""Seed-made weights for every model family, built on the device in ONE
jitted call, in the type the configuration states (`param_dtype`, absent
= float32).

The benchmark owns this: the program's own `init_params` is not used, so
the reference and the program both receive weights the program has not
made.  The tree layout is the program's parameter layout (the names a
checkpoint uses), because that is the interface the weights cross; it is
the family module's `param_specs(hp)` (harness/families/).

Init (configs/<config>.json "init"): matrices normal(0, gain/sqrt(fan_in)),
fan_in the first axis, or for the kind `stacked` (experts or layers
stacked on leading axes) the last but one; embeddings and position tables
normal(0, embedding_std), layer-norm scale 1, biases 0.  Every leaf is
drawn in float32 and rounded ONCE to the parameter type, a leaf at a time
inside the one program, so that a tree of several GB is never held twice.

`init.summary_clock` asks the family to wire, into the tree it is handed,
the way a summary ends at the length the article's first word codes
(the family's `wire`, `length_code`, `word_for_length`); a family that
does not offer it is an error.  A configuration with no clock is served
as it is: every summary then runs to `max_dec_steps`.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference

STOP_ID = 3

Spec = Tuple[Tuple[int, ...], str]  # (shape, kind)


def _is_spec(x) -> bool:
    return (isinstance(x, tuple) and len(x) == 2
            and isinstance(x[0], tuple) and isinstance(x[1], str))


def n_params(specs) -> int:
    leaves = jax.tree_util.tree_leaves(specs, is_leaf=_is_spec)
    return int(sum(int(np.prod(s[0])) for s in leaves))


def param_dtype(cfg: Dict[str, Any]):
    """The type the configuration's parameters are made and served in."""
    return jnp.dtype(cfg.get("param_dtype", "float32"))


def summary_clock(cfg: Dict[str, Any]):
    """(the family module, init.summary_clock) of a configuration whose
    weights carry a summary clock, else (None, None)."""
    clock = cfg["init"].get("summary_clock")
    if not clock:
        return None, None
    fam = reference.family(cfg["family"])
    missing = [f for f in ("wire", "length_code", "word_for_length")
               if not hasattr(fam, f)]
    if missing:
        raise ValueError(
            f"init.summary_clock asks family {cfg['family']!r} for a "
            f"summary clock it does not offer (no {', '.join(missing)} in "
            f"{fam.__name__})")
    return fam, clock


def make_params(cfg: Dict[str, Any], seed: int):
    """The parameter tree for `cfg` (a loaded config file) from `seed`,
    made on the default device by one jitted function."""
    fam = reference.family(cfg["family"])
    specs = fam.param_specs(cfg["hparams"])
    init, dtype = cfg["init"], param_dtype(cfg)
    clocked = summary_clock(cfg)[0]
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
            else:  # matrix / lstm / vocab / stacked: normal(0, gain /
                # sqrt(fan_in)); stacked matrices [..., fan_in, fan_out]
                gain = float(init.get(f"{kind}_gain",
                                      init.get("matrix_gain", 1.0)))
                fan_in = shape[-2] if kind == "stacked" else shape[0]
                x = (gain / np.sqrt(fan_in)) * jax.random.normal(
                    k, shape, jnp.float32)
            out.append(x)
        tree = jax.tree_util.tree_unflatten(treedef, out)
        if clocked is not None:
            tree = clocked.wire(tree, cfg["hparams"], init)
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), tree)

    # --seed may exceed 2**31: fold it into the key in two 31-bit halves
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return jax.jit(build)(key)
