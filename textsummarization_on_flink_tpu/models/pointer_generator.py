"""Pointer-generator seq2seq model (See et al. 2017), TPU-native.

Functional JAX re-design of the reference SummarizationModel
(/root/reference/src/main/python/pointer-generator/model.py) and
attention_decoder (attention_decoder.py).  Differences from the reference
are architectural, not semantic:

  * the 100-step Python-unrolled decoder graph (model.py:214,
    attention_decoder.py:141-174) is a single `lax.scan`;
  * training never materializes the extended-vocab final distribution
    (model.py:162-183); the gold-token probability is computed directly
    (see ops/losses.gold_mixture_prob);
  * the in-article OOV budget is static (`hps.max_oov_buckets`) instead of
    the dynamic per-batch `max_art_oovs` placeholder (model.py:45);
  * decode-time single-step semantics (initial_state_attention=True,
    attention_decoder.py:138-160) are preserved exactly, including the
    quirk that the previous step's attention is recomputed to update
    coverage while the current step's attention does not update it.

Parameter tree field names mirror the TF1 variable layout so checkpoint
import is a pure renaming exercise (checkpoint/tf1_import.py).

All public functions are pure and jittable; `hps` is static.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from textsummarization_on_flink_tpu import config as config_lib
from textsummarization_on_flink_tpu import models as models_lib
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.ops import attention as attn_ops
from textsummarization_on_flink_tpu.ops import losses as loss_ops
from textsummarization_on_flink_tpu.ops import lstm as lstm_ops
from textsummarization_on_flink_tpu.ops import topk as topk_ops

Array = jax.Array
Params = Dict[str, Any]


class EncoderOutput(NamedTuple):
    enc_states: Array  # [B, T_enc, 2H]
    enc_features: Array  # precomputed W_h h_i, [B, T_enc, 2H]
    dec_in_state: Tuple[Array, Array]  # (c, h) each [B, H]


class DecodeStepOutput(NamedTuple):
    topk_ids: Array  # [B, 2*beam]
    topk_log_probs: Array  # [B, 2*beam]
    state: Tuple[Array, Array]  # new (c, h)
    attn_dist: Array  # [B, T_enc]
    p_gen: Array  # [B]
    coverage: Array  # [B, T_enc] updated coverage (zeros if coverage off)


class TrainOutput(NamedTuple):
    loss: Array  # NLL (the reference's self._loss)
    coverage_loss: Array  # 0.0 when coverage off
    total_loss: Array  # loss + cov_loss_wt * coverage_loss
    attn_dists: Array  # [B, T_dec, T_enc] (for inspection/attn-vis)
    p_gens: Array  # [B, T_dec]


# --------------------------------------------------------------------------
# Initialization (model.py:204-231 initializer choices)
# --------------------------------------------------------------------------

def _trunc_normal(key: Array, shape, std: float) -> Array:
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)


def _uniform(key: Array, shape, mag: float) -> Array:
    return jax.random.uniform(key, shape, jnp.float32, -mag, mag)


def _glorot(key: Array, shape) -> Array:
    if len(shape) == 1:
        fan_in = fan_out = shape[0]
    else:
        fan_in, fan_out = shape[-2], shape[-1]
    limit = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -limit, limit)


def init_params(hps: HParams, vsize: int, key: Array) -> Params:
    """Build the parameter pytree. Names map 1:1 onto the TF1 checkpoint
    variable layout (see checkpoint/tf1_import.py for the exact mapping)."""
    H, E = hps.hidden_dim, hps.emb_dim
    D = 2 * H  # attention vector size == encoder state size (attention_decoder.py:63)
    keys = iter(jax.random.split(key, 24))
    tn = hps.trunc_norm_init_std
    mag = hps.rand_unif_init_mag

    params: Params = {
        "embedding": _trunc_normal(next(keys), (vsize, E), tn),
        "encoder": {
            "fw": {"kernel": _uniform(next(keys), (E + H, 4 * H), mag),
                   "bias": jnp.zeros((4 * H,), jnp.float32)},
            "bw": {"kernel": _uniform(next(keys), (E + H, 4 * H), mag),
                   "bias": jnp.zeros((4 * H,), jnp.float32)},
        },
        "reduce": {
            "w_reduce_c": _trunc_normal(next(keys), (2 * H, H), tn),
            "w_reduce_h": _trunc_normal(next(keys), (2 * H, H), tn),
            "bias_reduce_c": _trunc_normal(next(keys), (H,), tn),
            "bias_reduce_h": _trunc_normal(next(keys), (H,), tn),
        },
        "decoder": {
            "cell": {"kernel": _uniform(next(keys), (E + H, 4 * H), mag),
                     "bias": jnp.zeros((4 * H,), jnp.float32)},
            "attention": {
                "W_h": _glorot(next(keys), (D, D)),
                "v": _glorot(next(keys), (D,)),
                "w_c": _glorot(next(keys), (D,)),
                "linear_kernel": _glorot(next(keys), (2 * H, D)),
                "linear_bias": jnp.zeros((D,), jnp.float32),
            },
            "input_linear": {"kernel": _glorot(next(keys), (E + D, E)),
                             "bias": jnp.zeros((E,), jnp.float32)},
            "pgen_linear": {"kernel": _glorot(next(keys), (D + H + H + E, 1)),
                            "bias": jnp.zeros((1,), jnp.float32)},
            "output_linear": {"kernel": _glorot(next(keys), (H + D, H)),
                              "bias": jnp.zeros((H,), jnp.float32)},
        },
        "output_projection": {
            "w": _trunc_normal(next(keys), (H, vsize), tn),
            "v": _trunc_normal(next(keys), (vsize,), tn),
        },
    }
    return params


def add_coverage_params(params: Params, key: Array) -> Params:
    """Fresh w_c for coverage conversion (run_summarization.py:157-178)."""
    new = jax.tree_util.tree_map(lambda x: x, params)  # shallow-ish copy
    D = new["decoder"]["attention"]["W_h"].shape[0]
    new["decoder"]["attention"]["w_c"] = _glorot(key, (D,))
    return new


# --------------------------------------------------------------------------
# Forward pieces
# --------------------------------------------------------------------------

def _linear(p: Dict[str, Array], *args: Array) -> Array:
    """attention_decoder.py:184-228 `linear`: concat args, matmul, bias."""
    x = jnp.concatenate(args, axis=-1) if len(args) > 1 else args[0]
    return x @ p["kernel"] + p["bias"]


def _cast(hps: HParams, x: Array) -> Array:
    return x.astype(jnp.bfloat16) if hps.compute_dtype == "bfloat16" else x


def _proj(hps: HParams, x: Array, w: Array) -> Array:
    """x @ w with bf16 operands + f32 accumulation in bfloat16 mode — the
    [H, vocab] output projection is the FLOP-dominant matmul (SURVEY §7.2
    step 7 note).  Delegates to the ONE dtype-aware vocab matmul
    (ops/losses.project_scores) so the streaming chunked loss projects
    identically."""
    return loss_ops.project_scores(x, w, hps.compute_dtype)


def encode(params: Params, hps: HParams, enc_batch: Array, enc_lens: Array,
           enc_padding_mask: Array) -> EncoderOutput:
    """Embed + biLSTM + state reduction (model.py:210-221)."""
    emb = params["embedding"][enc_batch]  # [B, T, E]
    emb = _cast(hps, emb)
    enc_states, fw_st, bw_st = lstm_ops.bidirectional_encoder(
        params["encoder"]["fw"], params["encoder"]["bw"], emb, enc_lens,
        enc_padding_mask, unroll=hps.scan_unroll)
    # The decoder attention re-streams enc_states AND enc_feats from HBM
    # on EVERY decode step (T_dec x 2 x [B, T, D] — the step's dominant
    # bandwidth consumer), so in bf16 mode keep both in bf16: half the
    # bytes.  The attention energies/softmax still run in f32 — the op's
    # f32 dec_feats promote the arithmetic, so only the HBM
    # representation narrows, not the softmax math.
    if hps.compute_dtype != "bfloat16":
        enc_states = enc_states.astype(jnp.float32)
    # _reduce_states (model.py:97-121): ReLU linear from fw||bw to H
    r = params["reduce"]
    old_c = jnp.concatenate([fw_st[0], bw_st[0]], axis=-1)
    old_h = jnp.concatenate([fw_st[1], bw_st[1]], axis=-1)
    new_c = jax.nn.relu(old_c @ r["w_reduce_c"] + r["bias_reduce_c"])
    new_h = jax.nn.relu(old_h @ r["w_reduce_h"] + r["bias_reduce_h"])
    enc_feats = attn_ops.encoder_features(
        params["decoder"]["attention"], enc_states)
    return EncoderOutput(enc_states, enc_feats, (new_c, new_h))


def _decoder_core(params: Params, hps: HParams, enc: EncoderOutput,
                  enc_padding_mask: Array, state: Tuple[Array, Array],
                  context: Array, coverage: Array, x: Array,
                  ) -> Dict[str, Array]:
    """One train-mode decoder step (attention_decoder.py:141-174):
    merged input+context `x` -> cell -> attention (updates coverage) ->
    p_gen -> output projection input.  coverage always flows; with
    coverage off it is simply unused by the attention energies.

    `x` is the input_linear output; forward_train hoists its embedding
    half out of the scan (one [B, T, E] @ [E, E] matmul) and adds the
    context half per step."""
    dp = params["decoder"]
    cell_out, new_state = lstm_ops.lstm_cell(dp["cell"], x, state)
    new_context, attn_dist, new_cov = attn_ops.attend(
        dp["attention"], enc.enc_states, enc.enc_features, enc_padding_mask,
        new_state, coverage if hps.coverage else None, hps.coverage)
    if new_cov is None:
        new_cov = coverage
    p_gen = jax.nn.sigmoid(
        _linear(dp["pgen_linear"], new_context, new_state[0], new_state[1], x)
    )[:, 0]
    output = _linear(dp["output_linear"], cell_out, new_context)
    return dict(x=x, state=new_state, context=new_context, attn_dist=attn_dist,
                coverage=new_cov, p_gen=p_gen, output=output)


def forward_train(params: Params, hps: HParams, arrays: Dict[str, Array],
                  ) -> TrainOutput:
    """Full training/eval forward pass (model.py:199-277 semantics).

    The decoder scan carries only the recurrent state; everything batched
    over steps is hoisted out of it:
      * the embedding half of input_linear runs as one [B, T, E] matmul
        before the scan;
      * the FLOP-dominant [H, V] output projection, its softmax, and the
        NLL run AFTER the scan as one [T_dec, B, H] @ [H, V] matmul —
        per-step projection feeds the MXU M=B rows per 128-row tile
        (~12% fill at the reference batch); hoisted it is M=T_dec*B;
      * the coverage loss is the closed-form exclusive prefix sum of the
        stacked attention outputs (loss_ops.coverage_loss).
    Memory note: the hoisted scores tensor is [T_dec, B, V] f32 (~320 MB
    at reference scale), the price of the MXU-shaped matmul.
    """
    B = arrays["enc_batch"].shape[0]
    T_enc = arrays["enc_batch"].shape[1]
    enc = encode(params, hps, arrays["enc_batch"], arrays["enc_lens"],
                 arrays["enc_padding_mask"])
    emb_dec = params["embedding"][arrays["dec_batch"]]  # [B, T_dec, E]
    w = params["output_projection"]["w"]
    v = params["output_projection"]["v"]
    ip = params["decoder"]["input_linear"]
    E = emb_dec.shape[-1]
    emb_proj = emb_dec @ ip["kernel"][:E] + ip["bias"]  # [B, T_dec, E]
    k_ctx = ip["kernel"][E:]

    def step(carry, emb_proj_t):
        state, context, coverage = carry
        x = emb_proj_t + context @ k_ctx
        res = _decoder_core(params, hps, enc, arrays["enc_padding_mask"],
                            state, context, coverage, x)
        return ((res["state"], res["context"], res["coverage"]),
                (res["output"], res["attn_dist"], res["p_gen"]))

    D = enc.enc_states.shape[-1]
    init = (enc.dec_in_state, jnp.zeros((B, D), jnp.float32),
            jnp.zeros((B, T_enc), jnp.float32))
    _, (outputs, attn_dists, p_gens) = jax.lax.scan(
        step, init, jnp.swapaxes(emb_proj, 0, 1),
        unroll=max(hps.scan_unroll, 1))

    # hoisted projection + loss over all steps at once.  Memory note:
    # the [T_dec, B, V] f32 scores tensor (~320 MB at reference scale)
    # is also held as an autodiff residual (logsumexp/take_along_axis
    # grads need it), so training peak HBM grows by roughly 2x its size;
    # --remat recomputes it in backward instead (trade ~one extra
    # projection matmul for the residual) for larger batches/vocabs, and
    # --loss_chunk streams the projection+loss in T_dec chunks so the
    # full scores tensor never materializes in EITHER pass (the byte
    # diet, PERF.md) — token-exact vs the materialized path.
    dec_mask = arrays["dec_padding_mask"]
    targets_t = jnp.swapaxes(arrays["target_batch"], 0, 1)  # [T_dec, B]

    if hps.loss_chunk > 0:
        if hps.pointer_gen:
            gold = loss_ops.streaming_gold_probs(
                outputs, attn_dists, p_gens, targets_t,
                arrays["enc_batch_extend_vocab"], w, v,
                chunk=hps.loss_chunk, compute_dtype=hps.compute_dtype)
            loss = loss_ops.pointer_nll(jnp.swapaxes(gold, 0, 1), dec_mask)
        else:
            loss = loss_ops.streaming_softmax_cross_entropy(
                outputs, targets_t, jnp.swapaxes(dec_mask, 0, 1), w, v,
                chunk=hps.loss_chunk, compute_dtype=hps.compute_dtype)
    else:
        def scores_loss(outputs, attn_dists, p_gens):
            scores = _proj(hps, outputs, w) + v  # [T_dec, B, V]
            if hps.pointer_gen:
                gold = loss_ops.gold_mixture_prob_from_scores(
                    scores, attn_dists, p_gens, targets_t,
                    arrays["enc_batch_extend_vocab"])
                return loss_ops.pointer_nll(jnp.swapaxes(gold, 0, 1),
                                            dec_mask)
            return loss_ops.softmax_cross_entropy_baseline(
                jnp.swapaxes(scores, 0, 1), arrays["target_batch"], dec_mask)

        if hps.remat:
            scores_loss = jax.checkpoint(scores_loss)
        loss = scores_loss(outputs, attn_dists, p_gens)
    attn_b = jnp.swapaxes(attn_dists, 0, 1)  # [B, T_dec, T_enc]
    if hps.coverage:
        cov_loss = loss_ops.coverage_loss(attn_b, dec_mask)
    else:
        cov_loss = jnp.zeros(())
    total = loss + hps.cov_loss_wt * cov_loss
    return TrainOutput(loss=loss, coverage_loss=cov_loss, total_loss=total,
                       attn_dists=attn_b,
                       p_gens=jnp.swapaxes(p_gens, 0, 1))


# --------------------------------------------------------------------------
# Decode mode (beam search building blocks)
# --------------------------------------------------------------------------

def run_encoder(params: Params, hps: HParams, arrays: Dict[str, Array],
                ) -> EncoderOutput:
    """Beam-search encoder pass (model.py:347-364)."""
    return encode(params, hps, arrays["enc_batch"], arrays["enc_lens"],
                  arrays["enc_padding_mask"])


def final_distribution(hps: HParams, vocab_dist: Array, attn_dist: Array,
                       p_gen: Array, enc_batch_extend_vocab: Array) -> Array:
    """Extended-vocab mixture distribution [B, V + max_oov_buckets]
    (model.py:146-183; the static OOV budget replaces max_art_oovs): what
    a decode step ranks — ``step_top_k`` builds it only for a short row."""
    return topk_ops.extended_mixture(
        vocab_dist, attn_dist, p_gen, enc_batch_extend_vocab,
        vocab_dist.shape[-1] + hps.max_oov_buckets)


def step_top_k(hps: HParams, vocab_scores: Array, attn_dist: Array,
               p_gen: Array, ext_ids: Array, k: int,
               art_scores: Optional[Array] = None) -> Tuple[Array, Array]:
    """Every family's decode step ends here: the k best (probabilities,
    extended ids) of each row, ``top_k(final_distribution(hps, softmax(
    vocab_scores), ...), k)``.  ext_ids: [B, T_enc], or [T_enc] shared;
    art_scores: ``head_scores`` where the caller holds a head."""
    if not hps.pointer_gen:
        vocab_dist = jax.nn.softmax(vocab_scores, axis=-1)
        with jax.named_scope("topk"):
            return topk_ops.top_k(vocab_dist, k)
    return topk_ops.mixture_top_k(vocab_scores, attn_dist, p_gen, ext_ids, k,
                                  vocab_scores.shape[-1] + hps.max_oov_buckets,
                                  art_scores)


def head_at(hps: HParams, w: Array, v: Array, ext_ids: Array,
            ) -> Optional[topk_ops.ArticleHead]:
    """A family's ``beam_head`` over its own projection ``x @ w + v``:
    the head's columns at the articles' ids [.., T_enc], which a search
    gathers ONCE before its loop and hands to every step (the ids do not
    change while an article decodes); None where the step would read
    none (ops/topk.head_at), and then the step is as without."""
    if not hps.pointer_gen:
        return None
    return topk_ops.head_at(w, v, ext_ids, 2 * hps.beam_size)


def head_scores(hps: HParams, x: Array,
                head: Optional[topk_ops.ArticleHead]) -> Optional[Array]:
    """The vocabulary scores of x [K, H] at the article's ids [K,
    T_enc], by the projection the row itself comes from (``_proj``, so
    the configuration's precision), onto ``head_at``'s columns."""
    if head is None:
        return None
    return _proj(hps, x, head.w.T) + head.v


@jax.named_scope("vocab_dist")
def _vocab_scores(params: Params, hps: HParams, cell_out: Array,
                  context: Array, new_state: Tuple[Array, Array], x: Array,
                  head: Optional[topk_ops.ArticleHead] = None,
                  ) -> Tuple[Array, Array, Optional[Array]]:
    """The decode step's output head, shared by decode_onestep and
    decode_onestep_shared: p_gen and the output projection.  Returns
    (vocab_scores, p_gen, ``head_scores``); ``step_top_k`` normalises
    and ranks."""
    dp = params["decoder"]
    p_gen = jax.nn.sigmoid(
        _linear(dp["pgen_linear"], context, new_state[0], new_state[1], x))[:, 0]
    output = _linear(dp["output_linear"], cell_out, context)
    vocab_scores = _proj(hps, output, params["output_projection"]["w"]) + \
        params["output_projection"]["v"]
    return vocab_scores, p_gen, head_scores(hps, output, head)


def decode_onestep(params: Params, hps: HParams, enc: EncoderOutput,
                   enc_padding_mask: Array, enc_batch_extend_vocab: Array,
                   latest_tokens: Array, state: Tuple[Array, Array],
                   prev_coverage: Array) -> DecodeStepOutput:
    """One beam-search decoder step with the reference's decode-mode
    (initial_state_attention=True) semantics, attention_decoder.py:138-160:

      1. re-run attention at the PREVIOUS state to rebuild the previous
         context vector and update coverage (this is the only place
         coverage advances in decode mode);
      2. merge input+context, step the cell;
      3. attention at the new state WITHOUT updating coverage;
      4. p_gen, output projection, pointer mixture, top-2*beam.

    latest_tokens: [B] fixed-vocab ids (caller maps OOV->UNK,
    beam_search.py:112); state: (c, h) [B, H]; prev_coverage: [B, T_enc].
    """
    dp = params["decoder"]
    use_cov = hps.coverage
    # the named scopes below (here and in decode_onestep_shared) are
    # what a device trace splits the step by: OBSERVABILITY.md "Scopes"
    with jax.named_scope("attention"):
        ctx_prev, _, cov = attn_ops.attend(
            dp["attention"], enc.enc_states, enc.enc_features,
            enc_padding_mask, state, prev_coverage if use_cov else None,
            use_cov)
    if cov is None:
        cov = prev_coverage
    with jax.named_scope("lstm_cell"):
        inp_emb = params["embedding"][latest_tokens]
        x = _linear(dp["input_linear"], inp_emb, ctx_prev)
        cell_out, new_state = lstm_ops.lstm_cell(dp["cell"], x, state)
    with jax.named_scope("attention"):
        context, attn_dist, _ = attn_ops.attend(
            dp["attention"], enc.enc_states, enc.enc_features,
            enc_padding_mask, new_state, cov if use_cov else None, use_cov)
    vocab_scores, p_gen, _ = _vocab_scores(params, hps, cell_out, context,
                                           new_state, x)
    k = 2 * hps.beam_size  # model.py:284 (batch_size==beam_size there)
    topk_probs, topk_ids = step_top_k(hps, vocab_scores, attn_dist, p_gen,
                                      enc_batch_extend_vocab, k)
    return DecodeStepOutput(topk_ids=topk_ids,
                            topk_log_probs=jnp.log(topk_probs),
                            state=new_state, attn_dist=attn_dist, p_gen=p_gen,
                            coverage=cov)


def decode_onestep_shared(params: Params, hps: HParams, enc_one: EncoderOutput,
                          enc_mask: Array, ext_ids: Array,
                          latest_tokens: Array, state: Tuple[Array, Array],
                          prev_coverage: Array,
                          nb: Optional[Array] = None,
                          head: Optional[topk_ops.ArticleHead] = None,
                          ) -> DecodeStepOutput:
    """decode_onestep with the PER-ARTICLE encoder view shared across
    the K beam hypotheses (decode byte diet, ISSUE 7): enc_one leaves
    are [T_enc, ...] with no hypothesis axis, enc_mask/ext_ids [T_enc].
    The two attention queries broadcast against one encoder copy
    (ops/attention.attend_shared) instead of the K-fold
    `jnp.broadcast_to` the adapter used to materialize per step; only
    genuinely per-hypothesis tensors (cell state, coverage, the
    extended-vocab mixture) carry K.  Same decode-mode semantics
    (initial_state_attention=True) step for step.

    ``nb`` (length-masked slot decode, ISSUE 11): traced active-block
    count routing both attends through the blocked conditional chain
    (ops/attention._attend_shared_blocked) so per-step encoder traffic
    scales with the longest active resident's true length.

    ``head``: the article's ``beam_head`` from a caller that decodes in
    a loop; the article's words are then scored by a product with it
    and not by a gather from the step's row (ops/topk.py)."""
    dp = params["decoder"]
    use_cov = hps.coverage
    block = config_lib.resolve_enc_block(hps) if nb is not None else 0
    with jax.named_scope("attention"):
        ctx_prev, _, cov = attn_ops.attend_shared(
            dp["attention"], enc_one.enc_states, enc_one.enc_features,
            enc_mask, state, prev_coverage if use_cov else None, use_cov,
            nb=nb, block=block)
    if cov is None:
        cov = prev_coverage
    with jax.named_scope("lstm_cell"):
        inp_emb = params["embedding"][latest_tokens]
        x = _linear(dp["input_linear"], inp_emb, ctx_prev)
        cell_out, new_state = lstm_ops.lstm_cell(dp["cell"], x, state)
    with jax.named_scope("attention"):
        context, attn_dist, _ = attn_ops.attend_shared(
            dp["attention"], enc_one.enc_states, enc_one.enc_features,
            enc_mask, new_state, cov if use_cov else None, use_cov,
            nb=nb, block=block)
    vocab_scores, p_gen, art_scores = _vocab_scores(
        params, hps, cell_out, context, new_state, x, head)
    topk_probs, topk_ids = step_top_k(hps, vocab_scores, attn_dist, p_gen,
                                      ext_ids, 2 * hps.beam_size, art_scores)
    return DecodeStepOutput(topk_ids=topk_ids,
                            topk_log_probs=jnp.log(topk_probs),
                            state=new_state, attn_dist=attn_dist, p_gen=p_gen,
                            coverage=cov)


# --------------------------------------------------------------------------
# Beam-search adapter protocol (shared by all model families)
# --------------------------------------------------------------------------

class BeamStepOut(NamedTuple):
    """Model-agnostic one-step beam output (decode/beam_search.py).
    ``state`` is an opaque pytree whose every leaf has leading beam axis K,
    so the search can gather surviving hypotheses with one tree_map."""

    topk_ids: Array  # [K, 2*beam]
    topk_log_probs: Array  # [K, 2*beam]
    attn_dist: Array  # [K, T_enc]
    p_gen: Array  # [K]
    state: Any


def beam_encode(params: Params, hps: HParams, arrays: Dict[str, Array],
                ) -> EncoderOutput:
    """Batched encoder view for beam search (leaves lead with B; the
    search vmaps per article)."""
    return run_encoder(params, hps, arrays)


def beam_head(params: Params, hps: HParams, ext_ids: Array,
              ) -> Optional[topk_ops.ArticleHead]:
    """The output projection at the articles' ids (``head_at``)."""
    op = params["output_projection"]
    return head_at(hps, op["w"], op["v"], ext_ids)


def beam_adapter(hps: HParams):
    """(init_state, step) closures implementing the beam protocol for the
    LSTM pointer-generator.  State = decoder cell (c, h) + coverage.
    ``step``'s ``head`` is the article's ``beam_head``, or None."""
    K = hps.beam_size

    def init_state(params: Params, enc_one: EncoderOutput):
        del params
        H = enc_one.dec_in_state[0].shape[-1]
        T_enc = enc_one.enc_states.shape[0]
        return {
            "cell_c": jnp.broadcast_to(enc_one.dec_in_state[0][None], (K, H)),
            "cell_h": jnp.broadcast_to(enc_one.dec_in_state[1][None], (K, H)),
            "coverage": jnp.zeros((K, T_enc), jnp.float32),
        }

    def step(params: Params, enc_one: EncoderOutput, enc_mask: Array,
             ext_ids: Array, t: Array, latest: Array, state,
             nb=None, head=None) -> BeamStepOut:
        del t  # the LSTM state carries all positional context
        # per-article encoder view handed through UN-broadcast (decode
        # byte diet): only cell state + coverage carry the K axis
        out = decode_onestep_shared(params, hps, enc_one, enc_mask, ext_ids,
                                    latest,
                                    (state["cell_c"], state["cell_h"]),
                                    state["coverage"], nb=nb, head=head)
        return BeamStepOut(
            topk_ids=out.topk_ids, topk_log_probs=out.topk_log_probs,
            attn_dist=out.attn_dist, p_gen=out.p_gen,
            state={"cell_c": out.state[0], "cell_h": out.state[1],
                   "coverage": out.coverage})

    return init_state, step


#: the length-masked slot-decode adapter (ISSUE 11): the shared
#: protocol wrapper threads the traced block count into this family's
#: step, where it scales the two encoder attends with true length
beam_adapter_masked = models_lib.masked_adapter(beam_adapter)


def pad_enc_view(enc_view: EncoderOutput, t_target: int) -> EncoderOutput:
    """Zero-pad a bucket-width encoder view's time axis to ``t_target``
    (the prefill -> pack hand-off, decode/beam_search.prefill_jit).
    The biLSTM encoder is pad-invariant by construction (masked
    carry-through + length-aware reverse, ops/lstm.py), so a
    bucket-width encode equals the valid prefix of a full-width one and
    zeros are exactly what full-width encoding writes past the valid
    length; dec_in_state carries no time axis."""
    def pad(x):
        if x.shape[1] >= t_target:
            return x
        widths = [(0, 0)] * x.ndim
        widths[1] = (0, t_target - x.shape[1])
        return jnp.pad(x, widths)

    return EncoderOutput(enc_states=pad(enc_view.enc_states),
                         enc_features=pad(enc_view.enc_features),
                         dec_in_state=enc_view.dec_in_state)
