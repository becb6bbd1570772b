"""Average-attention (AAN) draft decoder family — the speculative tier's
cheap proposer (ISSUE 10; ROADMAP item 4).

*Accelerating Neural Transformer via an Average Attention Network*
(PAPERS.md): replace decoder self-attention with a CUMULATIVE-AVERAGE
layer — position t summarizes its prefix as the running mean of the
layer inputs, passed through a small FFN and merged with the current
input through a learned forget/input gate.  The decode step then carries
ONE running sum per layer instead of a growing KV cache, so per-token
cost and resident state are O(1) in history — the property that makes
this family the draft tier under continuous serving (a draft slot is
``L*H`` floats, vs the transformer's ``2*L*T*nh*hd`` cache).

Everything around the decoder self-attention is the transformer family
verbatim — the SAME encoder stack (``transformer._encoder_stack``), the
same per-layer cross-attention/copy mechanism, the same tied-embedding
loss head (``transformer.train_output_tail``), the same
``TransformerEncView`` encoder view — so the family plugs into beam
search, serving, checkpointing, and the sharding registry with zero new
plumbing (param leaf names match the transformer's where shared).

Two init modes:

  * ``init_params`` — fresh (training a draft from scratch / tests);
  * ``init_from_transformer`` — the distilled greedy-draft bootstrap: a
    tf1_import-style declarative mapping copies every shared leaf from a
    full-model checkpoint (embedding, positions, the WHOLE encoder, an
    evenly-strided subset of decoder layers' cross-attention/LN/FFN, the
    loss head) and fresh-initializes only the AAN average-FFN and gate,
    which have no full-model counterpart.  The mapped draft starts out
    proposing from the full model's own representations — acceptance is
    non-trivial from step zero, no distillation run required.

The NARROW variant (ISSUE 12; PERF.md "Distilled narrow draft"):
``draft_hidden`` < H runs the decoder blocks at width H_d while the
embedding, positions, and the WHOLE encoder stay H-wide (copied verbatim
from the full model under ``spec_draft="map"``), bridged by learned
boundary projections — an [H, H_d] ``emb_proj`` on decoder inputs and
[H, H_d] cross-attention K/V maps on the shared encoder output — and a
FACTORED vocab head (``draft_vocab_rank``): scores = (h @ [H_d, r]) @
[r, V] + out_bias, so the projection term scales with r*V instead of
H*V.  That projection is what made the equal-width draft lose on FLOPs
(BYTE_BUDGET.json spec kill condition); the narrow decoder has no
full-model counterpart and is trained by sequence-level distillation
(train/distill.DistillTrainer) through the SAME
``transformer.train_output_tail`` loss head.  Both variants keep the
beam-adapter contract, so every loop kind and ``spec_verify`` work
unmodified.

Numerics note: ``forward_train`` computes the prefix mean with
``jnp.cumsum`` (one parallel pass over T_dec) while the decode step adds
to a running f32 sum — different summation trees, so train/decode parity
is tight-tolerance, not bitwise (pinned by test).  Beam-loop parity
(while/scan/chunked/slot) IS exact: every loop kind drives the same
jitted step.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.models import pointer_generator as pg
from textsummarization_on_flink_tpu import models as models_lib
from textsummarization_on_flink_tpu.models import transformer as tf

Array = jax.Array
Params = Dict[str, Any]

TrainOutput = pg.TrainOutput
BeamStepOut = pg.BeamStepOut
TransformerEncView = tf.TransformerEncView


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def _decoder_hps(hps: HParams) -> HParams:
    """HParams view for the DECODER-side blocks: hidden_dim is the
    draft width H_d (config.resolve_draft_hidden) and ffn_width follows
    it (4*H_d when ffn_dim is auto), while the caller keeps the
    original hps for the H-wide embedding/encoder side.  At equal
    width this is the identity, so the legacy draft's shapes (and the
    family used as a FULL model) are untouched."""
    from textsummarization_on_flink_tpu.config import resolve_draft_hidden

    Hd = resolve_draft_hidden(hps)
    if Hd == hps.hidden_dim:
        return hps
    return hps.replace(hidden_dim=Hd, ffn_dim=hps.ffn_dim or 4 * Hd)


def _init_cross_attn(key: Array, H_in: int, H_d: int) -> Dict[str, Array]:
    """Cross-attention parameters whose K/V maps consume the H_in-wide
    shared encoder output and emit H_d-wide heads — the encoder-view
    boundary projection of the narrow draft (square at equal width,
    where it matches ``tf._init_attn``'s shapes)."""
    ks = jax.random.split(key, 4)
    return {
        "wq": pg._glorot(ks[0], (H_d, H_d)),
        "wk": pg._glorot(ks[1], (H_in, H_d)),
        "wv": pg._glorot(ks[2], (H_in, H_d)),
        "wo": pg._glorot(ks[3], (H_d, H_d)),
    }


def _init_aan_layer(key: Array, H: int, F: int) -> Dict[str, Any]:
    k_ffn, k_gate = jax.random.split(key)
    return {
        "ln1": tf._init_ln(H),
        # the average branch: FFN over the prefix mean, then a 2H->2H
        # input/forget gate over [x_t, ffn(avg_t)] (AAN §3.2)
        "aan_ffn": tf._init_ffn(k_ffn, H, F),
        "aan_gate": {"kernel": pg._glorot(k_gate, (2 * H, 2 * H)),
                     "bias": jnp.zeros((2 * H,), jnp.float32)},
        "ln_cross": tf._init_ln(H),
        "cross_attn": None,  # filled by caller (needs its own key)
        "ln2": tf._init_ln(H),
        "ffn": None,  # filled by caller
    }


def init_params(hps: HParams, vsize: int, key: Array) -> Params:
    """Fresh parameter pytree.  Shared leaves carry the transformer
    family's names/layout (embedding, pos_enc/pos_dec, encoder,
    decoder.layers[i].{ln1,ln_cross,cross_attn,ln2,ffn}, pgen_linear,
    out_bias) so sharding rules and the checkpoint mapping apply
    unchanged; aan_ffn/aan_gate are family-specific, and the narrow
    variant adds emb_proj (the [H, H_d] decoder-input adapter) and
    vocab_head (the factored [H_d, r]·[r, V] projection)."""
    H, F = hps.hidden_dim, hps.ffn_width
    dhps = _decoder_hps(hps)
    Hd, Fd = dhps.hidden_dim, dhps.ffn_width
    rank = hps.draft_vocab_rank
    n_keys = 3 + 2 * hps.enc_layers + 4 * hps.dec_layers + 3
    keys = iter(jax.random.split(key, n_keys))

    enc_layers = []
    for _ in range(hps.enc_layers):
        enc_layers.append({
            "ln1": tf._init_ln(H), "self_attn": tf._init_attn(next(keys), H),
            "ln2": tf._init_ln(H), "ffn": tf._init_ffn(next(keys), H, F),
        })
    dec_layers = []
    for _ in range(hps.dec_layers):
        layer = _init_aan_layer(next(keys), Hd, Fd)
        layer["cross_attn"] = _init_cross_attn(next(keys), H, Hd)
        layer["ffn"] = tf._init_ffn(next(keys), Hd, Fd)
        dec_layers.append(layer)
    params = {
        "embedding": pg._trunc_normal(next(keys), (vsize, H), 0.02),
        "pos_enc": pg._trunc_normal(next(keys), (hps.max_enc_steps, H), 0.02),
        "pos_dec": pg._trunc_normal(next(keys), (hps.max_dec_steps + 1, H),
                                    0.02),
        "encoder": {"layers": enc_layers, "ln_out": tf._init_ln(H)},
        "decoder": {"layers": dec_layers, "ln_out": tf._init_ln(Hd)},
        "pgen_linear": {"kernel": pg._glorot(next(keys), (2 * Hd, 1)),
                        "bias": jnp.zeros((1,), jnp.float32)},
        "out_bias": jnp.zeros((vsize,), jnp.float32),
    }
    if Hd != H:
        params["emb_proj"] = {"kernel": pg._glorot(next(keys), (H, Hd))}
    if rank:
        k1, k2 = jax.random.split(next(keys))
        params["vocab_head"] = {"w1": pg._glorot(k1, (Hd, rank)),
                                "w2": pg._glorot(k2, (rank, vsize))}
    return params


#: decoder-layer leaves copied 1:1 from the mapped full-model layer
#: (tf1_import-style declarative map — the strict check below guarantees
#: every draft leaf is either on this list or in _FRESH_KEYS)
_MAPPED_LAYER_KEYS = ("ln1", "ln_cross", "cross_attn", "ln2", "ffn")
#: family-specific leaves with no full-model counterpart — fresh init
_FRESH_KEYS = ("aan_ffn", "aan_gate")


def draft_layer_indices(full_layers: int, draft_layers: int) -> List[int]:
    """Evenly-strided subset of the full model's decoder layers the
    mapped draft keeps (first and last always included when
    draft_layers >= 2): the standard layer-skip draft recipe."""
    if draft_layers >= full_layers:
        return list(range(full_layers))
    if draft_layers == 1:
        return [full_layers - 1]  # the layer feeding the loss head
    step = (full_layers - 1) / (draft_layers - 1)
    return sorted({round(i * step) for i in range(draft_layers)})


def init_from_transformer(full_params: Params, full_hps: HParams,
                          draft_hps: HParams, key: Array) -> Params:
    """The distilled greedy-draft bootstrap: build AAN draft params from
    a FULL transformer checkpoint (checkpoint/tf1_import.py-style
    declarative mapping — copy shared leaves, fresh-init the rest,
    strict-check that nothing falls through).

    Copied: embedding/pos_enc/pos_dec, the whole encoder, out_bias —
    and at EQUAL width additionally pgen_linear, decoder ln_out, and,
    for each of the ``draft_hps.dec_layers`` evenly-strided kept layers,
    ln1/ln_cross/cross_attn/ln2/ffn.  Fresh: aan_ffn + aan_gate (no
    counterpart; the cumulative-average branch replaces self-attention).

    The NARROW variant (draft_hidden < hidden_dim) keeps the shared
    H-wide leaves (embedding, positions, encoder, out_bias) and
    fresh-initializes the ENTIRE H_d-wide decoder side — boundary
    projections, AAN blocks, cross-attention maps, pgen, the factored
    vocab head — because no full-model leaf has the right shape.  An
    undistilled narrow map therefore starts near zero acceptance
    (exactness still holds); train it with train/distill.DistillTrainer.
    A factored head at equal width (draft_vocab_rank > 0,
    draft_hidden = 0) keeps the mapped decoder layers and
    fresh-initializes only the head.
    """
    if full_hps.model_family != "transformer":
        raise ValueError(
            f"init_from_transformer maps transformer checkpoints only, "
            f"got model_family={full_hps.model_family!r} (use fresh init "
            f"or a separately trained draft for other families)")
    if draft_hps.hidden_dim != full_hps.hidden_dim:
        raise ValueError(
            f"mapped draft must share hidden_dim with the full model "
            f"(draft {draft_hps.hidden_dim} vs full {full_hps.hidden_dim})")
    H = draft_hps.hidden_dim
    dhps = _decoder_hps(draft_hps)
    Hd, Fd = dhps.hidden_dim, dhps.ffn_width
    rank = draft_hps.draft_vocab_rank
    cp = lambda x: jnp.asarray(x)  # noqa: E731 — copy-by-reference is fine
    keep = draft_layer_indices(full_hps.dec_layers, draft_hps.dec_layers)
    keys = iter(jax.random.split(key, len(keep) + 3))
    dec_layers = []
    for src_idx in keep:
        src = full_params["decoder"]["layers"][src_idx]
        k_layer = next(keys)
        layer = _init_aan_layer(k_layer, Hd, Fd)
        if Hd == H:
            for k in _MAPPED_LAYER_KEYS:
                layer[k] = jax.tree_util.tree_map(cp, src[k])
        else:
            # no H_d-shaped counterpart exists: the boundary projection
            # and blocks stay fresh (fold_in re-keys off the layer key)
            layer["cross_attn"] = _init_cross_attn(
                jax.random.fold_in(k_layer, 1), H, Hd)
            layer["ffn"] = tf._init_ffn(jax.random.fold_in(k_layer, 2),
                                        Hd, Fd)
        dec_layers.append(layer)
        # strict check (tf1_import discipline): every key accounted for
        unknown = set(layer) - set(_MAPPED_LAYER_KEYS) - set(_FRESH_KEYS)
        if unknown:
            raise KeyError(f"unmapped draft layer keys: {sorted(unknown)}")
    params = {
        "embedding": cp(full_params["embedding"]),
        "pos_enc": cp(full_params["pos_enc"]),
        "pos_dec": cp(full_params["pos_dec"]),
        "encoder": jax.tree_util.tree_map(cp, full_params["encoder"]),
        "out_bias": cp(full_params["out_bias"]),
    }
    k_tail = next(keys)
    if Hd == H:
        params["decoder"] = {
            "layers": dec_layers,
            "ln_out": jax.tree_util.tree_map(
                cp, full_params["decoder"]["ln_out"])}
        params["pgen_linear"] = jax.tree_util.tree_map(
            cp, full_params["pgen_linear"])
    else:
        params["decoder"] = {"layers": dec_layers, "ln_out": tf._init_ln(Hd)}
        params["pgen_linear"] = {
            "kernel": pg._glorot(jax.random.fold_in(k_tail, 0), (2 * Hd, 1)),
            "bias": jnp.zeros((1,), jnp.float32)}
        params["emb_proj"] = {
            "kernel": pg._glorot(jax.random.fold_in(k_tail, 1), (H, Hd))}
    if rank:
        vsize = full_params["out_bias"].shape[0]
        params["vocab_head"] = {
            "w1": pg._glorot(jax.random.fold_in(k_tail, 2), (Hd, rank)),
            "w2": pg._glorot(jax.random.fold_in(k_tail, 3), (rank, vsize))}
    return params


def make_draft_params(hps: HParams, full_params: Params,
                      seed: int = 0) -> Params:
    """Resolve ``hps.spec_draft`` to draft parameters: 'map' = the
    transformer->AAN checkpoint mapping above, 'fresh' = random init
    (tests/smokes; near-zero acceptance but exactness still holds).
    The ONE resolver — decode/decoder.py and scripts build drafts only
    through here."""
    from textsummarization_on_flink_tpu.config import derive_draft_hps

    dhps = derive_draft_hps(hps)
    if hps.spec_draft == "map":
        return init_from_transformer(full_params, hps, dhps,
                                     jax.random.PRNGKey(seed))
    if hps.spec_draft == "fresh":
        return init_params(dhps, hps.vocab_size, jax.random.PRNGKey(seed))
    raise ValueError(
        f"make_draft_params needs spec_draft='map'|'fresh', got "
        f"{hps.spec_draft!r}")


# --------------------------------------------------------------------------
# The cumulative-average block
# --------------------------------------------------------------------------

def _aan_gate(layer: Dict[str, Any], x_norm: Array, g: Array) -> Array:
    """Input/forget gating of the current input against the averaged
    branch (AAN §3.2): ``i, f = sigmoid(W [x; g])``, out = i*x + f*g."""
    dt = x_norm.dtype
    H = x_norm.shape[-1]
    gates = jax.nn.sigmoid(
        jnp.concatenate([x_norm, g], axis=-1)
        @ layer["aan_gate"]["kernel"].astype(dt)
        + layer["aan_gate"]["bias"].astype(dt))
    return gates[..., :H] * x_norm + gates[..., H:] * g


def _aan_block_train(layer: Dict[str, Any], x_norm: Array) -> Array:
    """Teacher-forced cumulative-average branch over the time axis
    (axis -2): prefix mean via one parallel cumsum (f32 accumulate),
    FFN, gate.  The decode step computes the same quantity from a
    running sum — see the module docstring's numerics note."""
    T = x_norm.shape[-2]
    csum = jnp.cumsum(x_norm.astype(jnp.float32), axis=-2)
    denom = (jnp.arange(T, dtype=jnp.float32) + 1.0)[:, None]
    avg = (csum / denom).astype(x_norm.dtype)
    g = tf._ffn_block(layer["aan_ffn"], avg)
    return _aan_gate(layer, x_norm, g)


# --------------------------------------------------------------------------
# Training forward (fully parallel over decode steps, like the transformer)
# --------------------------------------------------------------------------

def _embed_dec_draft(params: Params, hps: HParams, tokens: Array,
                     positions: Array) -> Array:
    """Decoder-input embedding: the shared H-wide embedding + positions,
    down-projected through the learned [H, H_d] ``emb_proj`` adapter
    when the narrow variant carries one (the embedding-boundary
    projection; identity at equal width)."""
    y = tf._embed_dec(params, hps, tokens, positions)
    ep = params.get("emb_proj")
    if ep is not None:
        y = y @ ep["kernel"].astype(y.dtype)
    return y


def forward_train(params: Params, hps: HParams, arrays: Dict[str, Array],
                  ) -> TrainOutput:
    """Teacher-forced forward -> TrainOutput through the SHARED loss head
    (transformer.train_output_tail): same pointer mixture, same
    --loss_chunk streaming, same coverage penalty.  The narrow variant
    runs the decoder blocks at H_d (``_decoder_hps``) against the
    H-wide encoder output — ``tf._mha`` is width-agnostic, the
    rectangular K/V kernels are the boundary."""
    enc_mask = arrays["enc_padding_mask"]
    T_dec = arrays["dec_batch"].shape[1]
    dhps = _decoder_hps(hps)

    x = tf._embed_enc(params, hps, arrays["enc_batch"])
    enc_out = tf._encoder_stack(params, hps, x, enc_mask)
    enc_out_c = pg._cast(hps, enc_out)

    y = _embed_dec_draft(params, hps, arrays["dec_batch"], jnp.arange(T_dec))
    cross_mask = enc_mask[:, None, :]

    def layer_fn(layer, y, enc_out_c, cross_mask):
        a = _aan_block_train(layer, tf._ln(layer["ln1"], y))
        y = y + a
        c, probs = tf._mha(dhps, layer["cross_attn"],
                           tf._ln(layer["ln_cross"], y), enc_out_c,
                           cross_mask)
        y = y + c
        y = y + tf._ffn_block(layer["ffn"], tf._ln(layer["ln2"], y))
        return y, c, probs

    if hps.remat:
        layer_fn = jax.checkpoint(layer_fn)
    attn_dist = None
    for layer in params["decoder"]["layers"]:
        y, c, probs = layer_fn(layer, y, enc_out_c, cross_mask)
        attn_dist = probs
        cross_ctx = c
    h = tf._ln(params["decoder"]["ln_out"], y).astype(jnp.float32)
    return tf.train_output_tail(params, hps, arrays, h, cross_ctx, attn_dist)


# --------------------------------------------------------------------------
# Decoding (O(1)-in-history step + beam adapter)
# --------------------------------------------------------------------------

def beam_encode(params: Params, hps: HParams, arrays: Dict[str, Array],
                ) -> TransformerEncView:
    """The transformer family's encoder-view precompute, ONE body
    (tf.beam_encode): per-layer cross-attention K/V from the shared
    H-wide encoder output, with the head split at the DRAFT width —
    the narrow variant's rectangular [H, H_d] K/V kernels make this
    the encoder-view boundary projection; identity at equal width."""
    return tf.beam_encode(params, hps, arrays, head_hps=_decoder_hps(hps))


def decode_onestep(params: Params, hps: HParams,
                   enc_one: TransformerEncView, enc_mask: Array,
                   ext_ids: Array, t: Array, latest: Array,
                   aan_sum: Array, nb=None, head=None,
                   ) -> Tuple[Array, Array, Array, Array, Array, Array]:
    """One AAN decode step for K hypotheses: O(1) in history — the only
    carried decode state is the [K, L, H] running sum (f32), updated by
    one add; no cache gather, no attention over past positions.

    Returns (topk_probs [K, 2*beam], topk_ids [K, 2*beam], attn_dist
    [K, T_enc], p_gen [K], h [K, H_d], new_sum [K, L, H_d]).
    """
    dhps = _decoder_hps(hps)
    y = _embed_dec_draft(params, hps, latest, t)  # [K, H_d]
    dt = y.dtype
    new_sums = []
    attn_dist = None
    for li, layer in enumerate(params["decoder"]["layers"]):
        with jax.named_scope("attention"):  # the average stands in for it
            x_norm = tf._ln(layer["ln1"], y)
            s = aan_sum[:, li] + x_norm.astype(jnp.float32)  # running sum
            new_sums.append(s)
            avg = (s / (t.astype(jnp.float32) + 1.0)).astype(dt)
            g = tf._ffn_block(layer["aan_ffn"], avg)
            y = y + _aan_gate(layer, x_norm, g)
        # cross attention + output head are the transformer family's
        # shared decode blocks — one numerics source for all three
        # decode paths (beam step / spec verify / this); dhps carries
        # the draft width so head splits/scales follow H_d
        cross_out, attn_dist = tf.cross_attend_layer(
            dhps, layer, y, enc_one.cross_k[li], enc_one.cross_v[li],
            enc_mask, nb=nb)
        y = y + cross_out
        y = y + tf._ffn_block(layer["ffn"], tf._ln(layer["ln2"], y))
        cross_ctx = cross_out
    topk_probs, topk_ids, p_gen, h = tf.decode_output_tail(
        params, dhps, y, cross_ctx, attn_dist, ext_ids, 2 * hps.beam_size,
        head)
    new_sum = jnp.stack(new_sums, axis=1)  # [K, L, H_d]
    return topk_probs, topk_ids, attn_dist, p_gen, h, new_sum


def beam_adapter(hps: HParams):
    """Beam protocol (init_state, step): the decode state is ONE
    [K, L, H_d] running-sum tensor — every loop kind (while/scan/
    chunked/slot) works unmodified, and a resident draft slot costs
    L*H_d floats instead of a KV cache (narrower still for the narrow
    draft)."""
    K = hps.beam_size
    L = hps.dec_layers
    H = _decoder_hps(hps).hidden_dim

    def init_state(params: Params, enc_one: TransformerEncView):
        del params, enc_one
        return {"aan_sum": jnp.zeros((K, L, H), jnp.float32)}

    def step(params: Params, enc_one: TransformerEncView, enc_mask: Array,
             ext_ids: Array, t: Array, latest: Array, state,
             nb=None, head=None) -> BeamStepOut:
        topk_probs, topk_ids, attn_dist, p_gen, _, new_sum = decode_onestep(
            params, hps, enc_one, enc_mask, ext_ids, t, latest,
            state["aan_sum"], nb=nb, head=head)
        return BeamStepOut(topk_ids=topk_ids,
                           topk_log_probs=jnp.log(topk_probs + 1e-10),
                           attn_dist=attn_dist, p_gen=p_gen,
                           state={"aan_sum": new_sum})

    return init_state, step


#: the length-masked slot-decode adapter (ISSUE 11) — the shared
#: protocol wrapper; nb reaches the transformer cross-attention block
beam_adapter_masked = models_lib.masked_adapter(beam_adapter)


#: the AAN encoder view IS the transformer's (same K/V precompute), so
#: the prefill pad hand-off is the transformer's too; so is the head
pad_enc_view = tf.pad_enc_view
beam_head = tf.beam_head
