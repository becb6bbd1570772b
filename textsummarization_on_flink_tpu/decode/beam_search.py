"""On-device batched beam search.

Semantics parity with the reference's Python-side beam search
(/root/reference/src/main/python/pointer-generator/beam_search.py), but the
entire search runs inside one jitted on-device loop per dispatch — a
`lax.scan` over max_dec_steps with masked updates, or a `lax.while_loop`
with early exit, auto-selected per backend (TS_BEAM_LOOP, see
_loop_kind) — instead of ~100 `sess.run` round trips per article
(SURVEY.md §3.4):

  * at step 0 only the first (all-identical) hypothesis is expanded
    (beam_search.py:125 `num_orig_hyps`);
  * each live hypothesis proposes `2*beam_size` continuations
    (beam_search.py:127-141, model.py:280-285);
  * candidates are processed in descending score order: a STOP candidate
    moves to the results pool only if at least `min_dec_steps` tokens were
    generated (earlier STOPs are *discarded*), anything else refills the
    live beam, and processing halts once either pool holds `beam_size`
    entries (beam_search.py:143-154);
  * the loop ends when `beam_size` results exist or `max_dec_steps` is
    reached; an empty results pool falls back to the live beam
    (beam_search.py:158-162);
  * final ranking is by length-normalized total log-prob, where the length
    includes the START token like the reference's
    `len(self.tokens)` (beam_search.py:71-79,164-168).

Because live hypotheses all share the same length at any step, ordering by
total log-prob during the search equals the reference's ordering by average
log-prob; the average only matters for the final cross-length ranking.

TPU-first details: all shapes are static — the per-step candidate triage
is a pure cumulative-sum computation over the `beam*2*beam` sorted
candidates (no data-dependent Python), and a whole batch of B articles is
searched per dispatch via `vmap`.  OOV ids are mapped back to UNK before
the embedding lookup inside the loop (beam_search.py:112).

Byte diet (ISSUE 7; PERF.md "Decode byte diet"): the loop body never
materializes per-hypothesis trajectories.  Instead of gathering and
rewriting full `[K, T]` token and `[K, T, T_enc]` attention histories
through `x[parent]` every step (per-step traffic scaling with
`beam x T_dec x T_enc`), each step appends ONE column of backpointers —
parent slot, chosen token, and the step's raw attention/p_gen rows — at
`[:, t]`, and a finished hypothesis is recorded as four scalars
(log-prob, length, finish step, parent slot).  `_finalize_beam`
reconstructs the single winning trajectory with one reverse `lax.scan`
over the backpointer columns at the very end.  Token-exact with the
materialized-history search (pinned by the parity suite).

Model-family-agnostic: the search drives the (init_state, step) beam
adapter of ``hps.model_family`` (models/__init__.get_family), carrying the
model's decode state — LSTM cell + coverage, or a transformer KV cache —
as an opaque pytree whose leaves lead with the beam axis.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from textsummarization_on_flink_tpu.config import HParams, resolve_beam_loop
from textsummarization_on_flink_tpu.data.vocab import START_ID, STOP_ID, UNK_ID
from textsummarization_on_flink_tpu.models import get_family

Array = jax.Array

NEG = -1e30  # effectively -inf, without inf-inf NaN hazards

# the loop-kind rule lives jax-free in config.py (bench.py's supervisor
# labels its rows by it without importing jax)
_loop_kind = resolve_beam_loop


class BeamSearchOutput(NamedTuple):
    """Best hypothesis per article (batch axis leading)."""

    tokens: Array  # [B, T_dec+1] extended-vocab ids, [0]=START
    length: Array  # [B] token count including START (== reference len(tokens))
    avg_log_prob: Array  # [B]
    attn_dists: Array  # [B, T_dec, T_enc] attention per generated token
    p_gens: Array  # [B, T_dec]


class _BeamState(NamedTuple):
    """Per-article search state, backpointer representation (ISSUE 7).

    History buffers (`parent_hist`/`tok_hist`/`attn_steps`/`pgen_steps`)
    are append-only: each step writes ONE column at `[:, t]` and nothing
    ever gathers them by parent — `_finalize_beam` backtracks the single
    winning trajectory at the end.  Their width is T+1: column T is a
    scratch column that masked (post-finish) loop iterations write into,
    and columns >= the finish step are dead — never read by the
    backtrack — so these buffers (and `dec_state`) stay OUT of the
    masked-update select in `_masked_scan_body` (see `_SELECT_FIELDS`).
    `attn_steps[:, t]` holds the step's raw attention rows indexed by the
    PRE-expansion (parent) beam slot; `tok_hist[:, t]`/`parent_hist[:, t]`
    are indexed by the post-expansion slot.
    """

    t: Array  # scalar int32: decode step (reference's `steps`)
    latest: Array  # [K] extended-vocab id of each live hyp's last token
    sum_lp: Array  # [K] total log prob of live hyps
    dec_state: Any  # model-family decode state; leaves lead with K
    n_res: Array  # scalar int32: filled result slots
    parent_hist: Array  # [K, T+1] int32 parent slot per step
    tok_hist: Array  # [K, T+1] int32 chosen token per step
    attn_steps: Array  # [K, T+1, T_enc] raw per-parent-slot attention rows
    pgen_steps: Array  # [K, T+1] raw per-parent-slot p_gen
    res_lp: Array  # [K+1] (slot K is a scratch slot)
    res_len: Array  # [K+1] int32, token count incl START
    res_t: Array  # [K+1] int32 finish step of each result
    res_par: Array  # [K+1] int32 parent (pre-expansion) slot at finish


def _init_beam_state(hps: HParams, T_enc: int, dec_state: Any,
                     attn_cols: Optional[int] = None) -> _BeamState:
    """The step-0 search state for one article (dec_state comes from the
    family's beam adapter; everything else is shape-only).

    attn_cols narrows the attention history to that many columns — the
    slot path keeps a single scratch column per slot and scatters each
    step's row into the shared page pool instead of carrying the full
    [K, T+1, T_enc] buffer per resident."""
    K = hps.beam_size
    T = hps.max_dec_steps
    return _BeamState(
        t=jnp.zeros((), jnp.int32),
        latest=jnp.full((K,), START_ID, jnp.int32),
        sum_lp=jnp.zeros((K,), jnp.float32),
        dec_state=dec_state,
        n_res=jnp.zeros((), jnp.int32),
        parent_hist=jnp.zeros((K, T + 1), jnp.int32),
        tok_hist=jnp.zeros((K, T + 1), jnp.int32),
        attn_steps=jnp.zeros(
            (K, T + 1 if attn_cols is None else attn_cols, T_enc),
            jnp.float32),
        pgen_steps=jnp.zeros((K, T + 1), jnp.float32),
        res_lp=jnp.full((K + 1,), NEG, jnp.float32),
        res_len=jnp.ones((K + 1,), jnp.int32),
        res_t=jnp.zeros((K + 1,), jnp.int32),
        res_par=jnp.zeros((K + 1,), jnp.int32),
    )


def _beam_cond(hps: HParams):
    """The search-still-running predicate (reference's `steps <
    max_dec_steps and len(results) < beam_size`, beam_search.py:118)."""

    def cond(s: _BeamState):
        return jnp.logical_and(s.t < hps.max_dec_steps,
                               s.n_res < hps.beam_size)

    return cond


def _make_beam_body(params, hps: HParams, step_fn, enc_one, enc_mask,
                    ext_ids, head, attn_col_fn=None):
    """One decode step for one article, closed over its encoder view —
    shared verbatim by the batch search (_search_one) and the slot loop
    (step_slots_jit), so the paths cannot drift.

    head: the article's row of the family's ``beam_head``, which both
    callers gather before their loop and the step only passes on.

    attn_col_fn(t) overrides the attention-history write column — the
    slot loop writes every step into its width-1 scratch column
    (index 0) and scatters that row into the page pool OUTSIDE this
    body; an explicit override, never out-of-bounds index
    semantics, keeps the write well-defined."""
    K = hps.beam_size
    V = hps.vocab_size
    S = K * 2 * K  # candidate count per step

    def body(s: _BeamState) -> _BeamState:
        latest = jnp.where(s.latest >= V, UNK_ID,
                           s.latest)  # beam_search.py:112
        step = step_fn(params, enc_one, enc_mask, ext_ids, s.t, latest,
                       s.dec_state, head=head)
        with jax.named_scope("beam_select"):
            # candidate pool: every live hyp x its 2K continuations
            cand_lp = s.sum_lp[:, None] + step.topk_log_probs  # [K, 2K]
            # step 0: all hyps identical -> expand only hyp 0
            # (beam_search.py:125)
            first = jnp.arange(K)[:, None] == 0
            cand_lp = jnp.where(jnp.logical_or(s.t > 0, first), cand_lp, NEG)
            flat_lp = cand_lp.reshape(S)
            flat_tok = step.topk_ids.reshape(S)
            order = jnp.argsort(-flat_lp)  # stable descending
            srt_lp = flat_lp[order]
            srt_tok = flat_tok[order]
            parent = order // (2 * K)  # originating live hyp

            # sequential triage (beam_search.py:143-154) as cumsums: counts
            # only advance for selected candidates, and a candidate is
            # processed only while both pools are still short of K.
            is_stop = srt_tok == STOP_ID
            valid_stop = jnp.logical_and(is_stop, s.t >= hps.min_dec_steps)
            non_stop = jnp.logical_not(is_stop)
            live_rank = jnp.cumsum(non_stop)  # inclusive
            res_rank = jnp.cumsum(valid_stop)
            live_sel = non_stop & (live_rank <= K) & (s.n_res + res_rank < K)
            res_sel = valid_stop & (s.n_res + res_rank <= K) & (live_rank < K)

            # --- rebuild the live beam ---
            # first K selected
            sel = jnp.argsort(jnp.logical_not(live_sel))[:K]
            ok = live_sel[sel]  # all True unless results filled first
            par = parent[sel]
            new_latest = srt_tok[sel]
            new_sum_lp = jnp.where(ok, srt_lp[sel], NEG)

            # --- append ONE backpointer column (no history gathers) ---
            # s.t == T only on masked post-horizon iterations; column T is
            # the scratch column those writes land in (never read back)
            parent_hist = s.parent_hist.at[:, s.t].set(par)
            tok_hist = s.tok_hist.at[:, s.t].set(new_latest)
            attn_col = s.t if attn_col_fn is None else attn_col_fn(s.t)
            attn_steps = s.attn_steps.at[:, attn_col].set(step.attn_dist)
            pgen_steps = s.pgen_steps.at[:, s.t].set(step.p_gen)

            # --- record finished hypotheses as scalar backpointers ---
            slot = jnp.where(res_sel, s.n_res + res_rank - 1, K)  # K = scratch
            res_lp = s.res_lp.at[slot].set(jnp.where(res_sel, srt_lp, NEG))
            res_len = s.res_len.at[slot].set(s.t + 2)  # START + t+1 generated
            res_t = s.res_t.at[slot].set(s.t)
            res_par = s.res_par.at[slot].set(parent)
            # scratch row K may hold garbage; restore invariants there
            res_lp = res_lp.at[K].set(NEG)

            return _BeamState(
                t=s.t + 1,
                latest=new_latest,
                sum_lp=new_sum_lp,
                dec_state=jax.tree_util.tree_map(lambda x: x[par], step.state),
                n_res=s.n_res + jnp.sum(res_sel).astype(jnp.int32),
                parent_hist=parent_hist,
                tok_hist=tok_hist,
                attn_steps=attn_steps,
                pgen_steps=pgen_steps,
                res_lp=res_lp,
                res_len=res_len,
                res_t=res_t,
                res_par=res_par,
            )

    return body


# the order-sensitive small leaves of _BeamState: the ONLY fields the
# masked scan select protects.  The history buffers and dec_state stay
# out on purpose (the decode byte diet's per-step win): a masked
# iteration's garbage writes land in dead columns — the scratch column T
# past the horizon, or the frozen-t column when the beam filled early,
# neither of which the finalize backtrack ever reads — and dec_state is
# never read again once cond(s) goes false.  Selecting them would re-read
# and re-write the full [K, T, T_enc] histories every masked step,
# reintroducing exactly the traffic the backpointer layout removes.
_SELECT_FIELDS = ("t", "latest", "sum_lp", "n_res",
                  "res_lp", "res_len", "res_t", "res_par")


def _masked_scan_body(cond, body):
    """Scan body with masked updates: once cond(s) goes false the
    order-sensitive state is carried through unchanged, so the result is
    token-exact with the while_loop (whose vmapped form masks every
    leaf).  body's garbage outputs past the horizon are discarded by the
    select (_SELECT_FIELDS) or land in dead history columns — see the
    _SELECT_FIELDS comment."""

    def scan_body(s, _):
        s2 = body(s)
        with jax.named_scope("beam_select"):
            keep = cond(s)
            kept = {
                f: jax.tree_util.tree_map(
                    lambda old, new: jnp.where(keep, new, old),
                    getattr(s, f), getattr(s2, f))
                for f in _SELECT_FIELDS
            }
        return s2._replace(**kept), None

    return scan_body


def _search_one(params, hps: HParams, init_state_fn, step_fn, loop, chunk,
                enc_one, enc_mask, ext_ids, head) -> BeamSearchOutput:
    """Beam search for ONE article (un-batched inputs; vmapped below).

    enc_one: the family's per-article encoder view (pytree, no batch
    axis); enc_mask: [T_enc]; ext_ids: [T_enc] extended-vocab ids;
    head: the article's row of the family's ``beam_head``.
    init_state_fn/step_fn: the family's beam adapter (models/__init__).
    loop: 'while', 'scan', or 'chunked' (see _loop_kind); chunk: the
    chunked inner-scan length, or None for the TS_BEAM_CHUNK env default
    (read here, at trace time).
    """
    T = hps.max_dec_steps
    T_enc = enc_mask.shape[0]
    init = _init_beam_state(hps, T_enc, init_state_fn(params, enc_one))
    cond = _beam_cond(hps)
    body = _make_beam_body(params, hps, step_fn, enc_one, enc_mask, ext_ids,
                           head)
    scan_body = _masked_scan_body(cond, body)

    if loop == "while":
        s = jax.lax.while_loop(cond, body, init)
    elif loop == "chunked":
        # while over fixed-size scan chunks: the RPC-proxied backend
        # charges ~1.4 ms per DYNAMIC loop iteration (host round trip on
        # the condition) but nothing per scan step, so ceil(T/C) dynamic
        # iterations buy while-style early exit (typical beams finish
        # well before max_dec_steps) at near-scan dispatch cost.  The
        # masked inner scan makes overshooting a chunk a no-op, so the
        # result stays token-exact with both other kinds.
        if chunk is None:  # env fallback, read at trace time
            chunk = resolved_chunk("chunked")
        C = min(max(int(chunk), 1), T)

        def chunk_body(s):
            s, _ = jax.lax.scan(scan_body, s, None, length=C)
            return s

        s = jax.lax.while_loop(cond, chunk_body, init)
    else:
        s, _ = jax.lax.scan(scan_body, init, None, length=T)

    return _finalize_beam(hps, s, T_enc)


def _finalize_beam(hps: HParams, s: _BeamState, T_enc: int,
                   ) -> BeamSearchOutput:
    """Rank the finished pool (falling back to the live beam), then
    reconstruct the ONE winning trajectory from the backpointer columns
    with a single reverse `lax.scan` over T — the reference's post-loop
    selection (beam_search.py:158-168) plus the ISSUE-7 backtrack pass.
    Shared by _search_one and unpack_slot_jit.
    """
    K = hps.beam_size
    T = hps.max_dec_steps
    # results empty -> fall back to the live beam (beam_search.py:158-160)
    use_live = s.n_res == 0
    live_len = s.t + 1  # START + t generated tokens
    pool_lp = jnp.where(use_live, jnp.concatenate([s.sum_lp, jnp.array([NEG])]),
                        s.res_lp)
    pool_len = jnp.where(use_live, jnp.full((K + 1,), live_len),
                         s.res_len)

    avg = pool_lp / pool_len.astype(jnp.float32)  # beam_search.py:77-79
    avg = jnp.where(pool_lp <= NEG / 2, NEG, avg)  # keep empty slots last
    best = jnp.argmax(avg)

    # Backtrack anchors: the step that produced the winner's LAST token,
    # the pre-expansion (parent) slot that produced it, and the token.
    # A live winner's last token came from post-expansion slot `best` at
    # step t-1 (t >= 1 always: the loop runs at least one step); a
    # result's came from the recorded (res_t, res_par) with a STOP token.
    live_slot = jnp.minimum(best, K - 1)  # best < K whenever live wins
    live_last_t = jnp.maximum(s.t - 1, 0)
    last_t = jnp.where(use_live, live_last_t, s.res_t[best])
    last_parent = jnp.where(use_live,
                            s.parent_hist[live_slot, live_last_t],
                            s.res_par[best])
    last_token = jnp.where(use_live, s.tok_hist[live_slot, live_last_t],
                           STOP_ID)

    def back(slot, t):
        # carry: the post-expansion slot the trajectory occupies at step
        # t (meaningful for t < last_t; re-anchored at t == last_t).
        at_last = t == last_t
        row_par = jnp.where(at_last, last_parent, s.parent_hist[slot, t])
        tok = jnp.where(at_last, last_token, s.tok_hist[slot, t])
        on_path = t <= last_t
        tok_out = jnp.where(on_path, tok, STOP_ID)  # STOP-fill past the end
        attn_row = jnp.where(on_path, s.attn_steps[row_par, t],
                             jnp.zeros((T_enc,), jnp.float32))
        pgen_val = jnp.where(on_path, s.pgen_steps[row_par, t], 0.0)
        return jnp.where(on_path, row_par, slot), (tok_out, attn_row,
                                                   pgen_val)

    _, (toks, attn, pgens) = jax.lax.scan(
        back, jnp.zeros((), jnp.int32), jnp.arange(T), reverse=True)
    tokens = jnp.concatenate([jnp.array([START_ID], jnp.int32), toks])
    return BeamSearchOutput(tokens=tokens,
                            length=pool_len[best],
                            avg_log_prob=avg[best],
                            attn_dists=attn,
                            p_gens=pgens)


def _search_batch(params, hps: HParams, arrays: Dict[str, Array],
                  loop: Optional[str] = None,
                  chunk: Optional[int] = None) -> BeamSearchOutput:
    """Encode a batch of B articles once, then vmap the per-article search.

    loop=None / chunk=None read TS_BEAM_LOOP / TS_BEAM_CHUNK at trace
    time (fine for callers that trace once, like the sharded step in
    parallel/mesh.py; jit callers that must react to env changes pass
    them explicitly — they are static cache-key arguments on
    run_beam_search_jit).
    """
    family = get_family(hps.model_family)
    enc_view = family.beam_encode(params, hps, arrays)
    init_state_fn, step_fn = family.beam_adapter(hps)
    ext = arrays["enc_batch_extend_vocab"]
    fn = functools.partial(_search_one, params, hps, init_state_fn, step_fn,
                           _loop_kind(loop), chunk)
    return jax.vmap(fn)(enc_view, arrays["enc_padding_mask"], ext,
                        family.beam_head(params, hps, ext))


@functools.partial(jax.jit, static_argnames=("hps", "loop", "chunk"))
def run_beam_search_jit(params, hps: HParams, arrays: Dict[str, Array],
                        loop: Optional[str] = None,
                        chunk: Optional[int] = None) -> BeamSearchOutput:
    return _search_batch(params, hps, arrays, loop, chunk)


# --------------------------------------------------------------------------
# Slot-state search: the continuous-batching kernel set (ISSUE 6),
# prefill/decode disaggregation (ISSUE 11), paged resident state (ISSUE 20)
# --------------------------------------------------------------------------
#
# The batch search above is all-or-nothing: one dispatch decodes B
# articles and returns when the SLOWEST finishes — the straggler barrier
# FastSeq (PAPERS.md) removes.  The slot API splits that dispatch into
# chunk-granular pieces over a persistent state for `slots` resident
# articles, so a host scheduler (serve/batcher.ContinuousBatcher) can
# retire finished articles and refill their slots between chunks.
#
# The request lifecycle has two stages:
#
#   PREFILL — encoder + cross-attention cache build, at the article's
#   micro-batcher bucket shape (config.parse_bucket_spec): one
#   prefill_jit compile per bucket, cost scaling with the bucket, never
#   with max_enc_steps.  The output is padded to the ONE resident width
#   and stamped with the article's true valid length.
#
#   DECODE — the persistent slot loop at one resident shape, carrying a
#   per-resident ``enc_valid_len``: each chunk's cross-attention runs a
#   conditional chain of encoder-key blocks bounded by the longest
#   ACTIVE resident's true length (see the family beam_adapter_masked
#   docs), so per-chunk bytes/FLOPs scale with real article lengths
#   instead of uniform padding — the FastSeq rule ("never let one
#   sequence's shape dictate the batch's cost") applied to the resident
#   set, at block granularity.
#
# The resident state is PAGED — the vLLM/PagedAttention block-table idea
# applied to this engine's T_enc axis, so a short article reserves its
# own pages and not a full-width row:
#
#   * every enc-axis leaf of the resident state — the family encoder
#     view (for tf/aan that IS the cross-attention KV cache), the
#     extended-vocab ids, and the [K, T+1, T_enc] attention history —
#     is a POOL of `resolve_enc_block`-row pages shared by all slots,
#     sized by the arena (decode/arena.PageArena;
#     config.resolve_arena_pages: by default slots x ceil(max_enc_steps
#     / block), every slot at full length);
#   * each slot's pages are named by a per-slot PAGE-TABLE row — int32
#     DATA passed as a traced argument, never shape;
#   * page index P (== arena capacity) is the SCRATCH page: every
#     unused table entry points at it, inactive slots are routed to it
#     inside the kernels, and its contents are garbage by contract —
#     exactly the dead-column story the byte-diet histories already
#     tell (see _SELECT_FIELDS);
#   * dec_state stays DENSE on purpose: its big leaves (the tf
#     self-attention KV cache) run over the DECODE axis, which the
#     bimodal mix does not vary — paging them buys nothing at this
#     workload while doubling the scatter traffic.  pg's [K, T_enc]
#     coverage is enc-axis but second-order (one f32 row vs the 2H-wide
#     encoder states); it rides dense too.
#
# Lifecycle (host side in decode/decoder.SlotDecodeEngine):
#
#     pages = resolve_arena_pages(hps, slots,
#                                 paged_page_bytes(params, hps))
#     pre   = prefill_jit(params, hps, bucket_arrays)       # per admit
#     state = init_slots_jit(params, hps, zero_arrays, pages)    # once
#     row   = arena.alloc(ceil(len/block)) padded with scratch   # admit
#     state = pack_slot_jit(params, hps, state, i, pre, row)
#     state, finished = step_slots_jit(params, hps, state, active,
#                                      table, chunk)  # table: [slots, B]
#     out   = unpack_slot_jit(hps, state, i, row); arena.free(row)
#
# Contracts:
#   * every DECODE kernel is shape-stable — slot index, active mask,
#     valid lengths and page-table contents are TRACED arguments, so
#     after the four warmup compiles NO request, slot choice, occupancy
#     pattern, article LENGTH pattern or allocation pattern triggers a
#     recompile; prefill_jit adds exactly one compile per serve bucket
#     (the warm set is 4 + len(buckets), pinned by test);
#   * per-slot activity masks: an inactive slot's ORDER-SENSITIVE state
#     (_SELECT_FIELDS: step counter, live beam, result pool) is carried
#     through step_slots_jit unchanged — the same masked-update select
#     as the 'chunked' batch loop, so a resident article's trajectory is
#     token-exact with _search_one on the same inputs.  The history
#     buffers and dec_state are NOT select-protected (the decode byte
#     diet): a masked iteration writes garbage into them, confined to
#     dead regions — the frozen-t column of the slot's own pages, the
#     scratch page and a never-again-read dec_state — so an inactive
#     slot's state is "unchanged" only where unpack_slot_jit reads, and
#     a slot's leaves are trustworthy ONLY between pack and the step
#     that finishes it (pack_slot_jit fully overwrites on reuse; do not
#     snapshot or inspect a slot's raw state outside that window);
#   * token-exactness is by construction, not tolerance: gathers through
#     the table reconstruct each ACTIVE slot's exact full-width view
#     (garbage beyond a slot's valid pages sits behind the valid-length
#     masks, whose exact-zero softmax contributes 0.0), and the per-step
#     attention row is scattered into the pool at the slot's own (page,
#     t) coordinates — the parity suite pins all three families bitwise
#     against the batch search at page boundaries;
#   * pack/unpack happen ONLY at chunk boundaries — the host never
#     observes (or mutates) mid-chunk state.
#
# The per-article search itself is the SAME _make_beam_body /
# _init_beam_state / _finalize_beam code the batch path runs; the slot
# layer adds routing, not semantics.


class PrefillState(NamedTuple):
    """One prefilled article (leading axis 1), ready for pack_slot_jit:
    the encoder + cross-attention cache built at the article's BUCKET
    shape by prefill_jit, zero-padded out to the resident width, plus
    the true valid length the decode stage masks by.  The zero tail is
    semantically dead (behind the valid-length mask) — padding here is
    what keeps pack_slot_jit at ONE compile across buckets."""

    enc_view: Any  # family encoder view, [1, T_enc_max, ...] leaves
    enc_mask: Array  # [1, T_enc_max]
    ext_ids: Array  # [1, T_enc_max]
    enc_valid_len: Array  # [1] int32


def _init_slot_beams(params, hps: HParams, enc_view, enc_mask,
                     attn_cols: Optional[int] = None):
    """vmapped step-0 beam state for a stack of articles."""
    family = get_family(hps.model_family)
    init_state_fn, _ = family.beam_adapter(hps)

    def one(enc_one, mask):
        return _init_beam_state(hps, mask.shape[0],
                                init_state_fn(params, enc_one),
                                attn_cols=attn_cols)

    return jax.vmap(one)(enc_view, enc_mask)


@functools.partial(jax.jit, static_argnames=("hps",))
def prefill_jit(params, hps: HParams,
                arrays: Dict[str, Array]) -> PrefillState:
    """The PREFILL stage (ISSUE 11): encoder + cross-attention cache for
    ONE article at its BUCKET shape — ``arrays`` leaves are [1, bucket]
    — then zero-padded to the resident width (hps.max_enc_steps) so
    pack_slot_jit stays at one compile.  The jit cache keys on the
    input shapes, so the warm set is exactly one executable per serve
    bucket; the encoder work (the LSTM scan / the T_enc^2 encoder
    self-attention — the cost the one-resident-shape engine used to pay
    at FULL width for every admission) scales with the bucket.

    Both families' encoders are pad-invariant (masked LSTM
    carry-through / masked softmax), so the valid prefix of the bucket
    encode is bitwise the valid prefix of a full-width encode — parity
    with the batch search is by construction, not by tolerance."""
    family = get_family(hps.model_family)
    with jax.named_scope("encoder"):
        enc_view = family.pad_enc_view(
            family.beam_encode(params, hps, arrays), hps.max_enc_steps)
    T = hps.max_enc_steps

    def pad_t(x):
        if x.shape[1] >= T:
            return x
        return jnp.pad(x, [(0, 0), (0, T - x.shape[1])])

    return PrefillState(
        enc_view=enc_view,
        enc_mask=pad_t(arrays["enc_padding_mask"]),
        ext_ids=pad_t(arrays["enc_batch_extend_vocab"]),
        enc_valid_len=arrays["enc_lens"].astype(jnp.int32))


class SlotState(NamedTuple):
    """Persistent decode state for `slots` resident articles.

    Slot-leading leaves (beam, enc_rest, enc_mask, enc_valid_len) carry
    one row a slot; the enc-axis leaves live in page pools shared by
    all slots, with one extra SCRATCH page at index [-1].  ``enc_rest``
    keeps the family enc_view's TREE STRUCTURE with each pooled leaf
    squeezed to width 0 on its time axis (zero bytes, but the treedef
    and the non-time leaves — e.g. pointer-generator's dec_in_state —
    survive in place, so the kernels can rebuild the exact full-width
    view by re-probing `pad_enc_view`, the same single source of truth
    prefill's padding uses).  The beam's attention history is a width-1
    scratch column; each step's row is scattered into ``attn_pool`` at
    the slot's pages.  ``enc_mask``/``enc_valid_len`` stay dense —
    they ARE the masks that make page garbage contribute exact zeros.
    """

    beam: Any  # _BeamState, [slots, ...] leaves; attn_steps [slots,K,1,T_enc]
    enc_rest: Any  # enc_view tree; pooled leaves squeezed to time-width 0
    enc_pages: Any  # tuple of pools [pages+1, block, *tail], pool [-1]=scratch
    ext_pool: Array  # [pages+1, block] int32 extended-vocab ids
    attn_pool: Array  # [pages+1, K, T+1, block] f32 attention history pages
    enc_mask: Array  # [slots, T_enc]
    enc_valid_len: Array  # [slots] int32


def _pool_spec(hps: HParams):
    """(block, pages-per-slot-max, padded width) of the page layout —
    block is resolve_enc_block (pages ARE the length-mask blocks, so
    the PR 11 block chain and the arena agree on granularity)."""
    from textsummarization_on_flink_tpu.config import resolve_enc_block

    block = resolve_enc_block(hps)
    b_max = -(-hps.max_enc_steps // block)
    return block, b_max, block * b_max


def _enc_time_axes(hps: HParams, enc_view):
    """Per-leaf encoder-time axis of a (possibly width-0) enc_view,
    probed by SHAPE through the family's own pad_enc_view: pad the view
    past any real width and see which axis grew.  None marks a leaf
    with no time axis (stays dense).  Pure eval_shape — runs at trace
    time, costs nothing, and cannot drift from the padding the prefill
    path actually performs."""
    family = get_family(hps.model_family)
    t_probe = hps.max_enc_steps + 17
    padded = jax.eval_shape(lambda v: family.pad_enc_view(v, t_probe),
                            enc_view)
    axes = []
    for a, b in zip(jax.tree_util.tree_leaves(enc_view),
                    jax.tree_util.tree_leaves(padded)):
        if tuple(a.shape) == tuple(b.shape):
            axes.append(None)
            continue
        diff = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                if x != y]
        if len(diff) != 1:
            raise ValueError(
                f"pad_enc_view changed more than one axis "
                f"({a.shape} -> {b.shape}); cannot page this leaf")
        axes.append(diff[0])
    return tuple(axes)


def _leaf_to_pages(leaf, ta: int, block: int, b_max: int):
    """One prefilled [1, ...] enc leaf -> its [b_max, block, *tail] page
    stack (time axis moved out front, zero-padded to the page grid)."""
    x = jnp.moveaxis(leaf, ta, 1)[0]  # [T_enc, *tail]
    pad = b_max * block - x.shape[0]
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x.reshape((b_max, block) + x.shape[1:])


def _pages_to_leaf(pool, pages, ta: int, T_enc: int):
    """Gather a dense [slots, ...] enc leaf back out of its pool through
    the page table (pages: [slots, b_max] int32; scratch rows carry
    garbage that sits behind the valid-length masks)."""
    g = pool[pages]  # [slots, b_max, block, *tail]
    g = g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])
    return jnp.moveaxis(g[:, :T_enc], 1, ta)


def paged_page_bytes(params, hps: HParams) -> int:
    """Bytes ONE arena page spans across all pools (enc-view pages +
    ext-id page + attention-history page) — the unit
    config.resolve_arena_pages divides the HBM byte budget by.  Pure
    eval_shape on the family's encoder view; jax-free callers pass the
    params tree they already hold."""
    block, _, _ = _pool_spec(hps)
    family = get_family(hps.model_family)
    probe = {
        "enc_batch": jax.ShapeDtypeStruct((1, hps.max_enc_steps),
                                          jnp.int32),
        "enc_lens": jax.ShapeDtypeStruct((1,), jnp.int32),
        "enc_padding_mask": jax.ShapeDtypeStruct((1, hps.max_enc_steps),
                                                 jnp.float32),
        "enc_batch_extend_vocab": jax.ShapeDtypeStruct(
            (1, hps.max_enc_steps), jnp.int32),
    }
    view = jax.eval_shape(
        lambda p, a: family.beam_encode(p, hps, a), params, probe)
    axes = _enc_time_axes(hps, view)
    total = 0
    for leaf, ta in zip(jax.tree_util.tree_leaves(view), axes):
        if ta is None:
            continue
        tail = int(np.prod([d for i, d in enumerate(leaf.shape)
                            if i not in (0, ta)], dtype=np.int64))
        total += block * tail * jnp.dtype(leaf.dtype).itemsize
    total += block * 4  # ext_pool page (int32)
    total += hps.beam_size * (hps.max_dec_steps + 1) * block * 4  # attn f32
    return int(total)


@functools.partial(jax.jit, static_argnames=("hps", "pages"))
def init_slots_jit(params, hps: HParams, arrays: Dict[str, Array],
                   pages: int) -> SlotState:
    """The all-empty persistent state from a [slots, T_enc] arrays dict
    (zeros are fine: inactive slots are never stepped unmasked and are
    fully overwritten by pack_slot_jit before first use): pools sized
    by the arena (`pages` is the ONE static knob — fixed for the
    engine's lifetime, so this stays one compile), everything else
    zeros.  Pool row `pages` is the scratch page."""
    family = get_family(hps.model_family)
    enc_view = family.beam_encode(params, hps, arrays)
    slots = arrays["enc_padding_mask"].shape[0]
    block, b_max, _ = _pool_spec(hps)
    axes = _enc_time_axes(hps, enc_view)
    leaves, treedef = jax.tree_util.tree_flatten(enc_view)
    rest, pools = [], []
    for leaf, ta in zip(leaves, axes):
        if ta is None:
            rest.append(leaf)
            continue
        tail = tuple(d for i, d in enumerate(leaf.shape)
                     if i not in (0, ta))
        pools.append(jnp.zeros((pages + 1, block) + tail, leaf.dtype))
        rest.append(jax.lax.slice_in_dim(leaf, 0, 0, axis=ta))
    K, T = hps.beam_size, hps.max_dec_steps
    return SlotState(
        beam=_init_slot_beams(params, hps, enc_view,
                              arrays["enc_padding_mask"], attn_cols=1),
        enc_rest=jax.tree_util.tree_unflatten(treedef, rest),
        enc_pages=tuple(pools),
        ext_pool=jnp.zeros((pages + 1, block), jnp.int32),
        attn_pool=jnp.zeros((pages + 1, K, T + 1, block), jnp.float32),
        enc_mask=arrays["enc_padding_mask"],
        enc_valid_len=jnp.zeros((slots,), jnp.int32))


@functools.partial(jax.jit, static_argnames=("hps",))
def pack_slot_jit(params, hps: HParams, state: SlotState, idx,
                  pre: PrefillState, row) -> SlotState:
    """Admit ONE prefilled article into slot `idx` with page-table row
    `row` ([b_max] int32 — the slot's freshly allocated pages, padded
    with the scratch id).  `idx` and `row` are both traced: one compile
    serves every slot, every bucket, AND every allocation pattern.
    Unused row entries all scatter into the scratch page (duplicate
    writes there are unordered and don't matter — scratch holds garbage
    by contract); stale attn pages from a page's previous tenant need
    no clearing, because unpack masks columns past the new tenant's
    valid length and the finalize backtrack masks steps past its
    horizon."""
    block, b_max, _ = _pool_spec(hps)
    axes = _enc_time_axes(hps, pre.enc_view)
    beam1 = _init_slot_beams(params, hps, pre.enc_view, pre.enc_mask,
                             attn_cols=1)

    def write(dst, src):
        return dst.at[idx].set(src[0])

    leaves = jax.tree_util.tree_leaves(pre.enc_view)
    rest_leaves, treedef = jax.tree_util.tree_flatten(state.enc_rest)
    rest_new, pool_new = [], []
    pool_it = iter(state.enc_pages)
    for leaf, rest_leaf, ta in zip(leaves, rest_leaves, axes):
        if ta is None:
            rest_new.append(rest_leaf.at[idx].set(leaf[0]))
            continue
        pool = next(pool_it)
        pool_new.append(pool.at[row].set(
            _leaf_to_pages(leaf, ta, block, b_max)))
        rest_new.append(rest_leaf)  # width-0: nothing to write
    ext = pre.ext_ids[0]
    pad = b_max * block - ext.shape[0]
    if pad:
        ext = jnp.pad(ext, (0, pad))
    return SlotState(
        beam=jax.tree_util.tree_map(write, state.beam, beam1),
        enc_rest=jax.tree_util.tree_unflatten(treedef, rest_new),
        enc_pages=tuple(pool_new),
        ext_pool=state.ext_pool.at[row].set(ext.reshape(b_max, block)),
        attn_pool=state.attn_pool,
        enc_mask=state.enc_mask.at[idx].set(pre.enc_mask[0]),
        enc_valid_len=state.enc_valid_len.at[idx].set(
            pre.enc_valid_len[0]))


@functools.partial(jax.jit, static_argnames=("hps", "chunk"))
def step_slots_jit(params, hps: HParams, state: SlotState,
                   active, table, chunk: int):
    """Advance every ACTIVE slot by up to `chunk` masked decode steps,
    gathering encoder state through the page table (`table`: [slots,
    b_max] int32, traced DATA — occupancy and allocation pattern can
    never recompile).  Returns (state', finished) where finished[i]
    marks an active slot whose search is done (horizon reached or beam
    full of results) — the host retires it via unpack_slot_jit and may
    refill.  Inactive slots run the same chunk on garbage state (the
    cost of shape stability): every ORDER-SENSITIVE update is discarded
    by the _SELECT_FIELDS mask — a NaN in a dead lane never escapes
    into the selected leaves (see the slot-contract comment above).

    Length-masked decode (ISSUE 11): the chunk's cross-attention block
    chain is bounded by ``nb`` = ceil(max active enc_valid_len /
    resolve_enc_block) — a TRACED scalar, uniform across the vmapped
    slots, so the conditional chain survives the vmap as real branches
    and one compile serves every length pattern.  Work executed per
    chunk scales with the longest ACTIVE resident's true article
    length; shorter co-residents' extra blocks are masked to the same
    energy floor the batch search gives padding, so trajectories stay
    token-exact with it.

    Structure: the full-width per-slot encoder views, and the family's
    output head at their ids (``beam_head``), are gathered ONCE per
    chunk (loop-invariant — the gather cost amortizes over the chunk's
    steps), then a top-level scan runs the chunk with a vmapped
    per-slot masked step inside, which exposes each step's attention
    row for ONE scatter into the shared pool at (slot pages, pre-step
    t).  Inactive slots' table rows are routed to the scratch
    page before either the gather or the scatter, so a harvested slot's
    stale table can never read from — or write garbage into — pages the
    arena has re-issued to a new tenant.  Masked (post-finish) lanes
    scatter garbage at their frozen t — a dead column of their OWN
    pages."""
    family = get_family(hps.model_family)
    _, step_fn = family.beam_adapter_masked(hps)
    cond = _beam_cond(hps)
    from textsummarization_on_flink_tpu.config import resolve_enc_block

    block = resolve_enc_block(hps)
    _, b_max, t_pad = _pool_spec(hps)
    T_enc = state.enc_mask.shape[1]
    slots = active.shape[0]
    K, T = hps.beam_size, hps.max_dec_steps
    scratch = state.attn_pool.shape[0] - 1  # page id P, static
    pages = jnp.where(active[:, None], table, scratch)

    valid = jnp.where(active, state.enc_valid_len,
                      jnp.zeros_like(state.enc_valid_len))
    nb = (jnp.max(valid) + block - 1) // block  # scalar, traced

    # rebuild the dense enc views once per chunk (loop-invariant)
    axes = _enc_time_axes(hps, state.enc_rest)
    rest_leaves, treedef = jax.tree_util.tree_flatten(state.enc_rest)
    dense_leaves = []
    pool_it = iter(state.enc_pages)
    with jax.named_scope("page_io"):
        for leaf, ta in zip(rest_leaves, axes):
            if ta is None:
                dense_leaves.append(leaf)
                continue
            dense_leaves.append(_pages_to_leaf(next(pool_it), pages, ta,
                                               T_enc))
        ext = state.ext_pool[pages].reshape(slots, t_pad)[:, :T_enc]
    enc_view = jax.tree_util.tree_unflatten(treedef, dense_leaves)
    head = family.beam_head(params, hps, ext)

    def one_step(beam, act, enc_one, mask, ext_one, head_one):
        def step_nb(p, e, m, x, t, latest, s, head):
            return step_fn(p, e, m, x, nb, t, latest, s, head=head)

        body = _make_beam_body(params, hps, step_nb, enc_one, mask,
                               ext_one, head_one, attn_col_fn=lambda t: 0)

        def masked_cond(s):
            return jnp.logical_and(act, cond(s))

        s2, _ = _masked_scan_body(masked_cond, body)(beam, None)
        return s2

    flat_pages = pages.reshape(-1)  # [slots*b_max]

    def chunk_body(carry, _):
        beams, attn_pool = carry
        t_old = beams.t  # [slots] pre-step write column (t <= T always)
        beams2 = jax.vmap(one_step)(beams, active, enc_view,
                                    state.enc_mask, ext, head)
        with jax.named_scope("page_io"):
            attn = beams2.attn_steps[:, :, 0, :]  # [slots, K, T_enc]
            pad = t_pad - T_enc
            if pad:
                attn = jnp.pad(attn, [(0, 0), (0, 0), (0, pad)])
            vals = attn.reshape(slots, K, b_max, block).transpose(
                0, 2, 1, 3)
            attn_pool = attn_pool.at[flat_pages, :,
                                     jnp.repeat(t_old, b_max)].set(
                vals.reshape(slots * b_max, K, block))
        return (beams2, attn_pool), None

    (beam, attn_pool), _ = jax.lax.scan(
        chunk_body, (state.beam, state.attn_pool), None, length=chunk)
    finished = jnp.logical_and(active,
                               jnp.logical_not(jax.vmap(cond)(beam)))
    return state._replace(beam=beam, attn_pool=attn_pool), finished


@functools.partial(jax.jit, static_argnames=("hps",))
def unpack_slot_jit(hps: HParams, state: SlotState, idx,
                    row) -> BeamSearchOutput:
    """The finished hypothesis for slot `idx`: gather the slot's
    attention pages back into the [K, T+1, T_enc] history the finalize
    backtrack expects (`row` is the slot's CURRENT table row — the host
    frees the pages only after this call), zero columns past the valid
    length (where the batch search's masked softmax writes exact zeros
    but a recycled page holds a previous tenant's rows), and run the
    SAME _finalize_beam as every other path.  `idx` and `row` are
    traced — one compile.  The slot is NOT cleared here; the host's
    activity mask retires it and the next pack overwrites the state."""
    K, T = hps.beam_size, hps.max_dec_steps
    _, b_max, t_pad = _pool_spec(hps)
    T_enc = state.enc_mask.shape[1]
    s = jax.tree_util.tree_map(lambda x: x[idx], state.beam)
    ap = state.attn_pool[row]  # [b_max, K, T+1, block]
    attn = jnp.moveaxis(ap, 0, 2).reshape(K, T + 1, t_pad)[:, :, :T_enc]
    valid = state.enc_valid_len[idx]
    attn = jnp.where(jnp.arange(T_enc)[None, None, :] < valid, attn, 0.0)
    return _finalize_beam(hps, s._replace(attn_steps=attn), T_enc)


def resolved_chunk(loop: str) -> Optional[int]:
    """The effective chunked inner-scan length, resolved from the env —
    pass this to run_beam_search_jit so the chunk size participates in
    the jit cache key (an env change between calls would otherwise be
    silently ignored by the cached executable).  The default lives in
    config.beam_chunk_from_env (single source, shared with bench.py's
    config fingerprint)."""
    if loop != "chunked":
        return None
    from textsummarization_on_flink_tpu.config import beam_chunk_from_env

    return beam_chunk_from_env()


def run_beam_search(params, hps: HParams, arrays: Dict[str, np.ndarray],
                    ) -> BeamSearchOutput:
    """Host entry: one compiled dispatch decodes the whole batch.

    Returns host numpy BeamSearchOutput; callers strip START/[STOP] and map
    ids back to words (decode/decoder.py, mirroring decode.py:109-119).
    """
    loop = _loop_kind()
    from textsummarization_on_flink_tpu import obs
    from textsummarization_on_flink_tpu.obs import profile as profile_lib

    # the shared compile ledger (obs/profile.py, ISSUE 16) carries the
    # jit-cache hit/miss telemetry this site used to hand-roll: cache
    # growth across the call = a fresh trace/compile
    chunk = resolved_chunk(loop)
    out = profile_lib.compiled_call(
        obs.registry_for(hps), "decode/beam_search_jit",
        run_beam_search_jit, params, hps, arrays,
        key=(loop, chunk), phase="decode/beam_search",
        loop=loop, chunk=chunk)
    return BeamSearchOutput(*[np.asarray(x) for x in out])
