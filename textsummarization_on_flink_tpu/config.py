"""Typed hyperparameter configuration.

Replaces the reference's 23 `tf.app.flags` definitions
(/root/reference/src/main/python/pointer-generator/run_summarization.py:48-88)
and the stringly-typed `TF_Hyperparameter` argv hand-off
(TFEstimator.java:52 -> run_summarization.py:418-420) with one frozen
dataclass.  Field names and defaults match the reference flag surface so
every reference invocation has a 1:1 equivalent here; `HParams.from_argv`
still accepts the reference's ``--flag=value`` argv string form for
pipeline-level compatibility.

TPU-specific additions (not in the reference):
  * ``max_oov_buckets`` — static in-article-OOV budget.  The reference uses
    a dynamic per-batch ``max_art_oovs`` (model.py:45,162); XLA needs static
    shapes, so we pad the extended vocabulary to a fixed budget.
  * ``compute_dtype`` — bf16 compute on the MXU (params stay f32).
  * mesh axis sizes (``dp``/``tp``/``sp``) for pjit/shard_map sharding.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class HParams:
    # Where to find data (run_summarization.py:48-50)
    data_path: str = ""
    vocab_path: str = ""

    # Important settings (run_summarization.py:52-56)
    mode: str = "train"  # train / eval / decode
    num_steps: int = 0  # 0 = never stop
    single_pass: bool = False
    inference: bool = False  # decode from raw text files

    # Where to save output (run_summarization.py:58-60)
    log_root: str = ""
    exp_name: str = ""

    # Model hyperparameters (run_summarization.py:62-74)
    hidden_dim: int = 256
    emb_dim: int = 128
    batch_size: int = 16
    max_enc_steps: int = 400
    max_dec_steps: int = 100
    beam_size: int = 4
    min_dec_steps: int = 35
    vocab_size: int = 50000
    lr: float = 0.15
    adagrad_init_acc: float = 0.1
    rand_unif_init_mag: float = 0.02
    trunc_norm_init_std: float = 1e-4
    max_grad_norm: float = 2.0

    # Pointer-generator / coverage (run_summarization.py:76-81)
    pointer_gen: bool = True
    coverage: bool = False
    cov_loss_wt: float = 1.0

    # Checkpoint surgery flags (run_summarization.py:83-85)
    convert_to_coverage_model: bool = False
    restore_best_model: bool = False

    # Debugging (run_summarization.py:88)
    debug: bool = False

    # ---- TPU-native additions ----
    max_oov_buckets: int = 128  # static extended-vocab budget
    compute_dtype: str = "float32"  # or "bfloat16"
    seed: int = 111  # reference seeds tf at 111 (run_summarization.py:329)
    dp: int = 1  # data-parallel mesh axis size
    tp: int = 1  # tensor-parallel mesh axis size (output projection)
    sp: int = 1  # sequence/context-parallel mesh axis size
    model_family: str = "pointer_generator"  # or "transformer"
    # transformer-family shape (BART-class encoder-decoder; hidden_dim is
    # d_model, embeddings are tied, ffn_dim=0 means 4*hidden_dim)
    enc_layers: int = 6
    dec_layers: int = 6
    num_heads: int = 8
    ffn_dim: int = 0
    # metrics fetch cadence in steps (one blocking D2H sync per window);
    # 0 = auto: 1 under --debug, 10 otherwise
    metrics_every: int = 0
    # checkpoint cadence in STEPS; REQUIRED (>0) on multi-host runs with
    # a checkpointer (collective saves must fire at the same step on
    # every host — Trainer hard-errors otherwise); 0 on single-host
    # keeps the wall-clock save_model_secs cadence
    checkpoint_steps: int = 0
    # ---- byte diet (PERF.md "Byte diet"; ISSUE 5) ----
    # Streaming chunked vocab loss: > 0 computes the training loss in
    # lax.scan chunks of this many decoder steps, so only a
    # [chunk, B, V] scores block ever exists (forward AND backward — a
    # custom VJP recomputes each chunk's scores instead of holding the
    # [T_dec, B, V] residual, ~2x320 MB at reference scale).  0 keeps
    # the materialized hoisted-projection path.  Token-exact and
    # grad-parity-pinned vs chunk=0 for both model families.
    loss_chunk: int = 0
    # Adagrad accumulator storage dtype: "bfloat16" halves the optimizer
    # state's HBM footprint and read/write traffic; the update math
    # still runs in f32 (accumulate -> rsqrt -> apply) and params stay
    # f32 masters.  N-step drift vs f32 is pinned by test.
    opt_state_dtype: str = "float32"
    # dp-gradient all-reduce dtype: "bfloat16" halves the per-step
    # gradient collective bytes.  A registry-level wire annotation
    # (parallel/sharding.py): the unified step stacks per-dp-group
    # grads under a P("dp", ...) constraint in this dtype and XLA's
    # partitioner inserts the dp all-reduce at it; f32 everywhere
    # else.  Works on any dp x tp mesh; requires sp=1 and pointer_gen
    # losses (whose per-example normalization makes group-mean ==
    # global-mean exactly).
    grad_allreduce_dtype: str = "float32"
    # rematerialize transformer layers in backward (jax.checkpoint):
    # trades ~1/3 more FLOPs for O(layers) less activation HBM — for the
    # long-context configs (enc 800+) where activations dominate
    remat: bool = False
    # Train-loop steps per host->device dispatch (the TPU-idiomatic
    # steps_per_execution pattern): k>1 runs k optimizer steps as ONE
    # on-device lax.scan over k stacked batches, cutting host round
    # trips k-fold.  Numerically identical to k=1
    # (same ops, same order).  Checkpoint/metrics cadences quantize to
    # dispatch boundaries; --debug forces k=1 (step-exact NaN watchdog).
    steps_per_dispatch: int = 1
    # lax.scan unroll factor for the LSTM encoder / decoder recurrences
    # (pointer-generator family).  The step is LATENCY-bound: ~500
    # sequential scan iterations of small matmuls dominate the step
    # (PERF.md Findings), so amortizing per-iteration loop
    # overhead across k unrolled bodies is the lever XLA can't pull
    # itself.  Numerically identical at any value; raises compile time
    # with k.  1 = no unrolling.
    scan_unroll: int = 8
    # runtime observability (obs/ registry + spans + exporters,
    # OBSERVABILITY.md): False runs this job dark — obs.registry_for(hps)
    # hands the component null metrics.  The process-wide kill switch is
    # TS_OBS=0 (read once, at default-registry creation).
    obs: bool = True
    # ---- live telemetry plane (OBSERVABILITY.md; ISSUE 9) ----
    # Exposition HTTP port for /metrics, /healthz, /snapshot, /spans
    # (obs/http.py; binds 127.0.0.1 only).  0 (default) = off; the
    # process-wide TS_OBS_HTTP=<port> env var enables it when this is
    # unset.  One server per process (first enabler wins).
    obs_http_port: int = 0
    # Flight-recorder ring capacity in frames (obs/flightrec.py): the
    # newest N per-step / per-tick frames kept in memory and dumped to
    # flight_<reason>.jsonl when a typed failure trigger fires (NaN
    # watchdog/rollback, serve dispatch failure, breaker open,
    # eviction storm).  0 disables frame recording and dumps.
    flight_frames: int = 64
    # SummaryWriter flush cadence in records: 1 flushes every write
    # (historical behavior), k>1 buffers k records per flush (the
    # reference flushes every 100 steps, run_summarization.py:242-244)
    summary_flush_every: int = 1
    # ---- performance attribution plane (obs/profile.py; ISSUE 16) ----
    # JAX/XLA profiler trace output dir for the trainer's steps-2..7
    # capture window (train/trainer.py).  "" = no capture; the legacy
    # TS_PROFILE_DIR env var is the fallback when unset, so existing
    # launch scripts keep working.  Each capture lands in the profiler
    # ledger as a `profiler_capture` note and a train/profiler_capture
    # span.
    profile_dir: str = ""
    # Analytic pricing for the divergence sentinel: True registers
    # __graft_entry__ cost-model providers (decode_step_cost /
    # prefill_cost / train_step_cost) per dispatch shape, priced ONCE
    # off the hot path, and publishes achieved bytes/s + FLOPs/s
    # gauges against them.  Off by default: pricing AOT-compiles the
    # costed program, which a short test job must not pay for.
    profile_analytic: bool = False
    # A dispatch counts as DIVERGED when its achieved bytes/s falls
    # more than this factor below the shape's calibrated baseline
    # (best of the first samples) — then the profiler dumps the flight
    # ring (flight_perf_divergence.jsonl) and surfaces the entry on
    # /alerts.  Must exceed 1; 5x tolerates normal jitter while still
    # catching silent recompiles and host-sync regressions.
    profile_divergence_factor: float = 5.0
    # ---- resilience (RESILIENCE.md; ISSUE 2) ----
    # fault-injection arming for THIS job: comma-separated
    # "point:prob:seed[:max]" specs (same syntax as the process-wide
    # TS_FAULTS env var; known points listed in resilience/faultinject.py).
    # "" (the default) leaves the job on the env plan — and with TS_FAULTS
    # also unset, every injection hook is a null-singleton no-op.
    faults: str = ""
    # Divergence recovery (train/trainer.py).  On a non-finite loss the
    # watchdog first discards the offending dispatch and SKIPS up to
    # nan_skip_steps consecutive batches (params revert to the pre-step
    # state), then ROLLS BACK to the last good checkpoint — cutting the
    # learning rate by nan_lr_cut per rollback — up to nan_max_rollbacks
    # times, and only then raises NanLossError.  Both 0 (the default)
    # keeps the reference's hard abort (train.py:107-108) and its exact
    # windowed-watchdog cost; arming either pins a per-dispatch metrics
    # sync and disables buffer donation (the pre-step state must survive
    # the dispatch), so recovery is an explicit opt-in for long
    # unattended runs.  Single-host, default-mesh only.
    nan_skip_steps: int = 0
    nan_max_rollbacks: int = 0
    # multiplicative LR cut applied at each divergence rollback (0.5 =
    # halve); must be in (0, 1]
    nan_lr_cut: float = 0.5
    # Per-request decode deadline in seconds (decode/decoder.py).  When
    # > 0 each decode_batch gets a Deadline; once a full-beam latency
    # estimate exists and the remaining budget cannot cover it, the
    # decoder degrades beam search to greedy (beam_size=1) and tags the
    # results degraded=True (counted in resilience/decode_degraded_total).
    # 0 (default) = no deadline, never degrade.
    decode_deadline_secs: float = 0.0
    # ---- concurrent serving (SERVING.md; ISSUE 4) ----
    # Requests coalesced per device dispatch by the serve/ micro-batcher
    # (0 = use batch_size; must be <= batch_size — the device batch
    # shape is always batch_size, short micro-batches are padded with
    # real_mask=False repeats).
    serve_max_batch: int = 0
    # Micro-batch coalescing window in milliseconds: after the first
    # request of a batch arrives, the batcher waits at most this long
    # for neighbors before dispatching a partial batch.  0 = dispatch
    # immediately (latency-first, fill suffers).
    serve_max_wait_ms: float = 20.0
    # Admission-controlled request queue depth: a non-blocking submit
    # against a full queue is rejected with the typed ServeOverloadError
    # (and counts against the admission circuit breaker — sustained
    # overload sheds immediately, BreakerSink semantics).
    serve_max_queue: int = 256
    # Encoder-length padding buckets for serving, as a comma-separated
    # ascending list of lengths (e.g. "100,200,400"); each micro-batch
    # pads to the smallest bucket covering its longest article, so the
    # beam-search jit cache stays bounded at len(buckets) entries per
    # beam width (hits/misses visible in decode/compile_cache_*_total).
    # "" = auto: {max_enc_steps//4, //2, max_enc_steps}, dropping
    # sub-64 buckets (except max_enc_steps itself).
    serve_buckets: str = ""
    # ---- continuous batching (SERVING.md "Continuous batching"; ISSUE 6) ----
    # Serving dispatch engine: "microbatch" (the ISSUE-4 baseline and
    # fallback — coalesce into fixed micro-batches, pay the
    # dispatch-window barrier) or "continuous" (persistent slotted
    # decode loop: finished sequences are masked out and their slots
    # refilled from the queue at chunk boundaries, so one long article
    # never holds neighbors hostage).
    serve_mode: str = "microbatch"
    # Resident decode slots for continuous mode (the [slots, beam, ...]
    # persistent state's leading axis).  0 = batch_size.  More slots
    # amortize the per-chunk dispatch over more articles but grow the
    # resident state linearly.
    serve_slots: int = 0
    # Decode steps per continuous-mode chunk: finished slots are
    # harvested and refilled every this-many steps.  Smaller = lower
    # refill latency, more host round trips.  0 = the TS_BEAM_CHUNK
    # default (beam_chunk_from_env, same source as the chunked beam
    # loop), clamped to max_dec_steps.
    serve_refill_chunk: int = 0
    # ---- decode byte diet (PERF.md "Decode byte diet"; ISSUE 7) ----
    # Transformer beam-search KV-cache storage dtype: "bfloat16" halves
    # the per-hypothesis [K, L, T, nh, hd] self-attention cache — the
    # dominant per-hypothesis resident tensor in continuous serving —
    # and its per-step gather/re-read traffic.  The attention logits and
    # softmax still run in f32 (the cache widens at the einsum), so only
    # the HBM representation narrows; N-step drift vs the f32 cache is
    # pinned by test.  The pointer-generator family has no KV cache and
    # ignores this flag.
    decode_cache_dtype: str = "float32"
    # ---- prefill/decode disaggregation (SERVING.md; ISSUE 11) ----
    # Encoder-key block length for the LENGTH-MASKED slot decode step:
    # cross-attention (and the pg attends) over a resident's encoder
    # state runs as a chain of this-many-position blocks, each gated by
    # a TRACED "block < ceil(max_active_valid_len / block)" predicate —
    # so per-chunk decode FLOPs/bytes scale with the longest ACTIVE
    # resident's true article length (block-granular) instead of the
    # uniform max_enc_steps padding, while the step kernel still
    # compiles exactly once.  Clamped to max_enc_steps; 64 keeps the
    # per-block matmuls MXU-shaped at reference scale (400 -> 7 blocks).
    decode_enc_block: int = 64
    # Continuous-mode prefill lookahead: how many requests beyond the
    # currently-free slots the ContinuousBatcher prefills per tick
    # (encoder + cross-attention cache at the article's bucket shape),
    # so a slot freed at the next chunk boundary refills from an
    # already-encoded article instead of paying prefill latency inline.
    # 0 = prefill exactly the free slots.
    serve_prefill_depth: int = 2
    # ---- the slot arena (SERVING.md "Paged resident state"; ISSUE 20) ----
    # Page count of the arena the continuous engine's enc-axis resident
    # leaves (encoder view / cross-attention KV cache, extended-vocab
    # ids, attention history) are pooled in: decode_enc_block-row pages
    # shared by all slots, ceil(true_len / block) of them an admission.
    # 0 = sized to hold every slot at full length, unless serve_arena_mb
    # sets a byte budget; fewer pages admit by free pages and let short
    # articles stop reserving long-article memory.  At least
    # ceil(max_enc_steps / decode_enc_block): one full-length article
    # must fit (config.resolve_arena_pages, the one resolver).
    serve_arena_pages: int = 0
    # The same arena as an HBM byte budget: floor(serve_arena_mb MiB /
    # beam_search.paged_page_bytes) pages.  Ignored when
    # serve_arena_pages is set.  0 = no byte budget.
    serve_arena_mb: float = 0.0
    # ---- speculative decode tier (SERVING.md "Quality tiers"; ISSUE 10) ----
    # Draft tokens proposed per verify cycle: the draft model (AAN
    # family) proposes spec_k tokens greedily, the full model scores all
    # spec_k+1 positions in one batched step and accepts the longest
    # agreeing prefix plus its own correction token — output token-exact
    # with full-model greedy decode by construction.
    spec_k: int = 4
    # Draft-model source for the spec/draft tiers: "" = no draft
    # configured (spec/draft tier requests are rejected typed at
    # submit); "map" = bootstrap the AAN draft from the full model's own
    # checkpoint (transformer family only — models/avg_attention.
    # init_from_transformer; re-mapped on every checkpoint hot-swap);
    # "fresh" = random init (tests/smokes; exactness holds, acceptance
    # is near zero).  Separately trained drafts inject params directly
    # (BeamSearchDecoder(draft_params=...)).
    spec_draft: str = ""
    # Decoder layers the draft keeps (evenly strided over the full
    # model's; 0 = all of them).  Fewer layers = cheaper draft steps =
    # lower FLOPs/token ratio in the spec gate (BYTE_BUDGET.json
    # "spec"), at the price of acceptance rate.
    draft_dec_layers: int = 0
    # ---- distilled narrow draft (PERF.md "Distilled narrow draft";
    # ISSUE 12) ----
    # Draft decoder hidden width H_d (0 = hidden_dim, the legacy
    # equal-width draft).  H_d < hidden_dim engages the NARROW variant:
    # the draft still shares the full model's embedding/positions and
    # encoder output verbatim (copied leaves), with learned
    # down-projections at the boundaries — an [H, H_d] embedding
    # adapter and [H, H_d] cross-attention K/V maps — so only the
    # per-token decoder blocks shrink.  The narrow decoder has no
    # full-model counterpart, so it must be TRAINED
    # (train/distill.DistillTrainer); requires draft_vocab_rank > 0
    # (the tied [V, H] projection cannot consume H_d states).
    draft_hidden: int = 0
    # Low-rank factored draft vocab head: scores = (h @ [H_d, r]) @
    # [r, V] + out_bias, so the draft's projection term scales with
    # r*V instead of H*V — the lever that moves the spec tier's FLOPs
    # break-even from ~96% acceptance to ~50% at the committed
    # ref-scale recipe (BYTE_BUDGET.json "spec" expected_speedup).
    # 0 = the tied full projection (legacy).
    draft_vocab_rank: int = 0
    # ---- acceptance-adaptive spec_k (SERVING.md "Quality tiers";
    # ISSUE 12) ----
    # True: the spec tier adapts the draft length per request from the
    # measured accept histogram (decode/speculative.SpecKController) —
    # k starts at spec_k, moves within [spec_k_min, spec_k_max] via
    # the expected-progress-per-FLOP model, and the adaptation happens
    # on the HOST between draft-verify cycles, so the jitted cycle
    # kernel compiles once per distinct k in the warm set (bounded by
    # the range).  Output stays token-exact with full-model greedy for
    # ANY k sequence (the verifier is unchanged).
    spec_k_adaptive: bool = False
    spec_k_min: int = 1
    spec_k_max: int = 8
    # Quality tier a request gets when it names none (serve/server.py
    # submit(tier=...)): beam (full search) > greedy (beam_size=1,
    # token-exact with spec) > spec (draft-then-verify fast path) >
    # draft (AAN greedy, no verify — gist quality).
    serve_default_tier: str = "beam"
    # Deadline-pressure degradation target (the beam->greedy ladder
    # generalized): a beam request whose remaining budget cannot cover
    # the observed full-beam latency is re-tiered HERE instead (and a
    # spec request to "draft"), per REQUEST, not per batch.
    serve_degrade_tier: str = "greedy"
    # ---- elastic serving fleet (SERVING.md "Elastic fleet"; ISSUE 13) ----
    # In-process ServingServer replicas behind the FleetRouter
    # (serve/fleet.py): 1 (default) = the single-server path, no router.
    # More replicas buy drain/upgrade/failover independence — a replica
    # can be hot-swapped or lost without touching its neighbors' queues.
    serve_replicas: int = 1
    # Request-hedging latency budget in milliseconds: once a routed
    # request has been outstanding this long, the router duplicates it
    # to a second replica and the FIRST resolution wins (the loser's
    # result is discarded — the exactly-once future never resolves
    # twice).  0 (default) = hedging off.  A hedge is a PURCHASED
    # duplicate (FastSeq: never do redundant work), so every hedge is
    # counted (serve/hedges_total, serve/hedge_wins_total) and the
    # spend is capped by serve_hedge_max_ratio.
    serve_hedge_ms: float = 0.0
    # Hedge-rate ceiling: hedged requests may never exceed this
    # fraction of fleet admissions (over-budget hedge candidates are
    # counted in serve/hedge_suppressed_total and left to their
    # primary).  The committed gate value lives in SERVE_SLO.json.
    serve_hedge_max_ratio: float = 0.1
    # ---- serving front door (SERVING.md "Front door"; ISSUE 14) ----
    # Bounded LRU summary-cache capacity in ENTRIES, keyed on
    # (content_hash, tier, params_fingerprint) — the fingerprint key is
    # what makes checkpoint hot-swap invalidate correctly by
    # construction (a swapped decoder reports a new fingerprint, so the
    # old entries simply stop matching).  A hit resolves the future
    # synchronously at submit without touching the queue, byte-identical
    # to a fresh decode of the same (article, tier, fingerprint) —
    # the pointer-generator's deterministic tiers are what make the
    # reuse exact, not approximate.  0 (default) = cache off, today's
    # behavior.
    serve_cache_entries: int = 0
    # Approximate byte ceiling for the summary cache (cached
    # decoded-word payloads); evicts LRU-first once exceeded.  0 = no
    # byte bound (the entry bound above still applies).
    serve_cache_bytes: int = 0
    # In-flight request coalescing: True attaches every submit whose
    # (content_hash, tier) matches a resident computation to that ONE
    # decode — all attached futures resolve exactly once from the
    # leader's result (leader failure fails the attached futures typed;
    # never hangs, never double-decodes).  False (default) keeps
    # today's one-decode-per-submit behavior.
    serve_coalesce: bool = False
    # Per-tenant token-bucket admission rate in requests/second
    # (ServeRequest.tenant; the default "" tenant is a tenant like any
    # other).  A submit finding its tenant's bucket empty is shed with
    # the typed TenantThrottledError BEFORE the queue/breaker — one
    # tenant's burst spends its own bucket, not the fleet's queue.
    # 0 (default) = unlimited, today's behavior.
    serve_tenant_rate: float = 0.0
    # Token-bucket burst depth (tokens a quiet tenant may accumulate).
    # 0 = auto: max(1, ceil(serve_tenant_rate)) — about one second of
    # burst (config.resolve_tenant_burst is the one resolver).
    serve_tenant_burst: int = 0
    # Weighted-fair queue pickup weights, "tenant:weight" comma-
    # separated (e.g. "free:1,paid:4"); unlisted tenants weigh 1.0.
    # The RequestQueue's consumer side picks across per-tenant FIFOs by
    # smooth weighted round-robin, so one tenant's deep backlog cannot
    # starve another's pickup.  "" = every tenant weighs 1.0 (and a
    # single-tenant queue is exactly the historical FIFO).
    serve_fair_weights: str = ""
    # ---- multi-process fleet transport (SERVING.md "Process fleet";
    # ISSUE 17) ----
    # "inproc" (default): replicas are threads in this process — the
    # fast path and the test substrate.  "proc": each replica is a
    # supervised OS child process (serve/procfleet.py, spawned via
    # `cli.py serve-replica`) reached over loopback sockets, so a
    # segfault, OOM, or wedged XLA call costs ONE replica, not the
    # fleet.
    serve_fleet_transport: str = "inproc"
    # Hard deadline on every supervisor->child HTTP scrape and ingress
    # socket connect, in milliseconds: a wedged child costs the router
    # exactly one timeout (counted in
    # serve/replica_scrape_errors_total and treated as unhealthy),
    # never a frozen FleetRouter.tick().
    serve_scrape_timeout_ms: float = 250.0
    # Scrape-result cache window in milliseconds: the remote handle
    # serves healthy()/load() off its last /healthz scrape until it is
    # this old (the router tick runs every ~5 ms; it must not issue N
    # HTTP GETs per tick).  0 = scrape on every read.
    serve_scrape_interval_ms: float = 50.0
    # ---- hierarchical document summarization (SERVING.md
    # "Hierarchical summarization"; ISSUE 19) ----
    # Words per document chunk in the map pass (serve/hiersum.py).
    # 0 = max_enc_steps (chunk at the full encoder width); explicit
    # values must fit the encoder (<= max_enc_steps) — a chunk wider
    # than the horizon would be silently truncated at tokenization and
    # its article_key would no longer describe what was decoded.
    hier_chunk_words: int = 0
    # Words of overlap between adjacent chunks: context carried across
    # the cut so a sentence split by a boundary is seen whole by one of
    # its chunks.  Must stay below the chunk width (stride =
    # chunk - overlap must be >= 1 or chunking cannot advance).
    hier_overlap_words: int = 0
    # Quality tier of the per-chunk map decodes ("" = greedy: chunks
    # are intermediate material, cheap extractive passes suffice) and
    # of the reduce decode ("" = beam: the caller-visible summary).
    # On a continuous-mode surface both collapse to beam (the resident
    # slot state is fixed-beam, server.py submit validation).
    hier_chunk_tier: str = "greedy"
    hier_reduce_tier: str = "beam"
    # sequence-parallel transformer encoder self-attention over the sp
    # mesh axis: "" (off), "ring" (K/V blocks rotate via ppermute with an
    # online softmax — no device ever holds the full [T, T] score
    # matrix), or "ulysses" (all-to-all re-shard from sequence to heads,
    # full attention per head group, all-to-all back; needs
    # num_heads % sp == 0).  Engages wherever an sp>1 mesh is active —
    # sharded train/eval steps AND the sharded beam search; on a single
    # device it falls back to flash/einsum attention.  Incompatible with
    # tp>1 (validated).
    sp_attention: str = ""

    # -- derived --
    @property
    def extended_vsize(self) -> int:
        return self.vocab_size + self.max_oov_buckets

    @property
    def ffn_width(self) -> int:
        """Transformer FFN hidden width (ffn_dim, or 4*hidden_dim when 0)."""
        return self.ffn_dim or 4 * self.hidden_dim

    def replace(self, **kw: Any) -> "HParams":
        return dataclasses.replace(self, **kw)

    def for_decode(self) -> "HParams":
        """Decode mode forces batch_size=beam_size in the reference
        (run_summarization.py:312-313); on-device beam search keeps an
        independent batch axis, but we mirror the mode switch."""
        return self.replace(mode="decode")

    # -- (de)serialization --
    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "HParams":
        d = json.loads(s)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @classmethod
    def from_argv(cls, argv: List[str]) -> "HParams":
        """Parse the reference's space-joined ``--flag value`` /
        ``--flag=value`` hyperparameter string (known flags only, like
        FLAGS(known_only=True) at run_summarization.py:420)."""
        fields = {f.name: f for f in dataclasses.fields(cls)}
        bool_literals = ("1", "0", "true", "false", "yes", "no")
        out: Dict[str, Any] = {}
        i = 0
        toks = [t for t in argv if t]
        while i < len(toks):
            tok = toks[i]
            if not tok.startswith("--"):
                i += 1
                continue
            body = tok[2:]
            is_bool = body.split("=", 1)[0] in fields and \
                fields[body.split("=", 1)[0]].type in ("bool", bool)
            if "=" in body:
                name, val = body.split("=", 1)
                i += 1
            elif (i + 1 < len(toks) and not toks[i + 1].startswith("--")
                  and not (is_bool and toks[i + 1].lower() not in bool_literals)):
                # separate-token value; for booleans only consume a literal,
                # so `--single_pass train_*.bin` reads as a bare True flag
                name, val = body, toks[i + 1]
                i += 2
            elif is_bool:  # bare boolean flag
                name, val = body, "True"
                i += 1
            else:  # non-bool flag with no value: skip it
                i += 1
                continue
            if name not in fields:
                continue
            ftype = fields[name].type
            if ftype in ("bool", bool):
                out[name] = str(val).lower() in ("1", "true", "yes")
            elif ftype in ("int", int):
                out[name] = int(val)
            elif ftype in ("float", float):
                out[name] = float(val)
            else:
                out[name] = val
        return cls(**out)

    def to_argv(self) -> str:
        """Render as the reference's hyperparameter string form.  Values
        with whitespace are shell-quoted; parse back with `from_string`."""
        import shlex

        parts = []
        for f in dataclasses.fields(self):
            v = str(getattr(self, f.name))
            quoted = shlex.quote(v) if v else ""  # empty stays `--flag=`
            parts.append(f"--{f.name}={quoted}")
        return " ".join(parts)

    @classmethod
    def from_string(cls, s: str) -> "HParams":
        """Parse a whole hyperparameter string (shlex-split, so quoted
        values containing spaces survive the round trip)."""
        import shlex

        return cls.from_argv(shlex.split(s))

    def validate(self) -> None:
        if self.mode not in ("train", "eval", "decode"):
            raise ValueError(f"mode must be train/eval/decode, got {self.mode!r}")
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"bad compute_dtype {self.compute_dtype!r}")
        if self.max_dec_steps < 1 or self.max_enc_steps < 1:
            raise ValueError("max_enc_steps/max_dec_steps must be >= 1")
        if self.min_dec_steps >= self.max_dec_steps:
            raise ValueError("min_dec_steps must be < max_dec_steps")
        from textsummarization_on_flink_tpu.models import FAMILIES

        if self.model_family not in FAMILIES:
            raise ValueError(f"unknown model_family {self.model_family!r}; "
                             f"expected one of {FAMILIES}")
        if self.model_family in ("transformer", "avg_attention"):
            if self.hidden_dim % self.num_heads != 0:
                raise ValueError(
                    f"num_heads={self.num_heads} must divide "
                    f"hidden_dim={self.hidden_dim}")
            if self.enc_layers < 1 or self.dec_layers < 1:
                raise ValueError("enc_layers/dec_layers must be >= 1")
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        if self.spec_draft not in ("", "map", "fresh"):
            raise ValueError(
                f"spec_draft must be ''|'map'|'fresh', got "
                f"{self.spec_draft!r}")
        if not 0 <= self.draft_dec_layers <= self.dec_layers:
            raise ValueError(
                f"draft_dec_layers must be in [0, dec_layers="
                f"{self.dec_layers}], got {self.draft_dec_layers}")
        if not 0 <= self.draft_hidden <= self.hidden_dim:
            raise ValueError(
                f"draft_hidden must be in [0, hidden_dim="
                f"{self.hidden_dim}] (0 = equal width), got "
                f"{self.draft_hidden}")
        if self.draft_hidden and self.draft_hidden % self.num_heads != 0:
            raise ValueError(
                f"num_heads={self.num_heads} must divide "
                f"draft_hidden={self.draft_hidden}")
        if self.draft_vocab_rank < 0:
            raise ValueError(
                f"draft_vocab_rank must be >= 0 (0 = tied projection), "
                f"got {self.draft_vocab_rank}")
        if (0 < self.draft_hidden < self.hidden_dim
                and self.draft_vocab_rank == 0):
            raise ValueError(
                "a narrow draft (draft_hidden < hidden_dim) requires a "
                "factored vocab head (draft_vocab_rank > 0): the tied "
                "[V, H] projection cannot consume H_d-wide states")
        if self.spec_k_min < 1 or self.spec_k_max < self.spec_k_min:
            raise ValueError(
                f"need 1 <= spec_k_min <= spec_k_max, got "
                f"[{self.spec_k_min}, {self.spec_k_max}]")
        if self.spec_k_adaptive and not (
                self.spec_k_min <= self.spec_k <= self.spec_k_max):
            raise ValueError(
                f"spec_k_adaptive needs the starting spec_k={self.spec_k} "
                f"inside [spec_k_min={self.spec_k_min}, "
                f"spec_k_max={self.spec_k_max}]")
        if self.serve_default_tier not in SERVE_TIERS:
            raise ValueError(
                f"serve_default_tier must be one of {SERVE_TIERS}, got "
                f"{self.serve_default_tier!r}")
        if (self.serve_degrade_tier not in SERVE_TIERS
                or self.serve_degrade_tier == "beam"):
            raise ValueError(
                f"serve_degrade_tier must be a tier BELOW beam "
                f"({SERVE_TIERS[1:]}), got {self.serve_degrade_tier!r}")
        if self.sp_attention not in ("", "ring", "ulysses"):
            raise ValueError(
                f"sp_attention must be '', 'ring', or 'ulysses', got "
                f"{self.sp_attention!r}")
        if self.scan_unroll < 1:
            raise ValueError(
                f"scan_unroll must be >= 1, got {self.scan_unroll}")
        if self.loss_chunk < 0:
            raise ValueError(
                f"loss_chunk must be >= 0 (0 = materialized loss), got "
                f"{self.loss_chunk}")
        if self.decode_cache_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"bad decode_cache_dtype {self.decode_cache_dtype!r} "
                f"(float32/bfloat16)")
        if self.opt_state_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"bad opt_state_dtype {self.opt_state_dtype!r} "
                f"(float32/bfloat16)")
        if self.grad_allreduce_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"bad grad_allreduce_dtype {self.grad_allreduce_dtype!r} "
                f"(float32/bfloat16)")
        if self.grad_allreduce_dtype == "bfloat16":
            if self.sp > 1:
                raise ValueError(
                    "grad_allreduce_dtype=bfloat16 supports dp x tp "
                    "meshes (sp=1): the per-group gradient vmap does not "
                    "compose with sequence-parallel attention's shard_map")
            if not self.pointer_gen:
                raise ValueError(
                    "grad_allreduce_dtype=bfloat16 requires pointer_gen "
                    "losses: the baseline CE normalizes by the GLOBAL "
                    "token count, which the per-shard objective cannot "
                    "express (shard-mean != global mean)")
        if self.steps_per_dispatch < 1:
            raise ValueError(f"steps_per_dispatch must be >= 1, got "
                             f"{self.steps_per_dispatch}")
        if not 0 <= self.obs_http_port <= 65535:
            raise ValueError(f"obs_http_port must be in [0, 65535] "
                             f"(0 = off), got {self.obs_http_port}")
        if self.flight_frames < 0:
            raise ValueError(f"flight_frames must be >= 0 (0 = off), got "
                             f"{self.flight_frames}")
        if self.summary_flush_every < 1:
            raise ValueError(f"summary_flush_every must be >= 1, got "
                             f"{self.summary_flush_every}")
        if self.profile_divergence_factor <= 1.0:
            raise ValueError(
                f"profile_divergence_factor must be > 1 (a dispatch "
                f"cannot 'diverge' by running at or above baseline), "
                f"got {self.profile_divergence_factor}")
        if self.nan_skip_steps < 0 or self.nan_max_rollbacks < 0:
            raise ValueError("nan_skip_steps/nan_max_rollbacks must be >= 0")
        if not 0.0 < self.nan_lr_cut <= 1.0:
            raise ValueError(
                f"nan_lr_cut must be in (0, 1], got {self.nan_lr_cut}")
        if self.decode_deadline_secs < 0:
            raise ValueError(f"decode_deadline_secs must be >= 0, got "
                             f"{self.decode_deadline_secs}")
        if self.serve_max_batch < 0 or self.serve_max_batch > self.batch_size:
            raise ValueError(
                f"serve_max_batch must be in [0, batch_size={self.batch_size}]"
                f", got {self.serve_max_batch}")
        if self.serve_max_wait_ms < 0:
            raise ValueError(f"serve_max_wait_ms must be >= 0, got "
                             f"{self.serve_max_wait_ms}")
        if self.serve_max_queue < 1:
            raise ValueError(f"serve_max_queue must be >= 1, got "
                             f"{self.serve_max_queue}")
        # parse for validation only — bad bucket specs fail at config
        # time, not at the first micro-batch
        parse_bucket_spec(self.serve_buckets, self.max_enc_steps)
        if self.serve_mode not in ("microbatch", "continuous"):
            raise ValueError(
                f"serve_mode must be 'microbatch' or 'continuous', got "
                f"{self.serve_mode!r}")
        if self.serve_slots < 0:
            raise ValueError(f"serve_slots must be >= 0 (0 = batch_size), "
                             f"got {self.serve_slots}")
        if self.serve_refill_chunk < 0:
            raise ValueError(
                f"serve_refill_chunk must be >= 0 (0 = TS_BEAM_CHUNK "
                f"default), got {self.serve_refill_chunk}")
        if self.decode_enc_block < 1:
            raise ValueError(
                f"decode_enc_block must be >= 1, got {self.decode_enc_block}")
        if self.serve_prefill_depth < 0:
            raise ValueError(
                f"serve_prefill_depth must be >= 0, got "
                f"{self.serve_prefill_depth}")
        if self.serve_arena_pages < 0:
            raise ValueError(
                f"serve_arena_pages must be >= 0 (0 = every slot at full "
                f"length), got {self.serve_arena_pages}")
        if self.serve_arena_mb < 0:
            raise ValueError(
                f"serve_arena_mb must be >= 0 (0 = no byte budget), got "
                f"{self.serve_arena_mb}")
        if self.serve_replicas < 1:
            raise ValueError(
                f"serve_replicas must be >= 1, got {self.serve_replicas}")
        if self.serve_cache_entries < 0:
            raise ValueError(
                f"serve_cache_entries must be >= 0 (0 = cache off), got "
                f"{self.serve_cache_entries}")
        if self.serve_cache_bytes < 0:
            raise ValueError(
                f"serve_cache_bytes must be >= 0 (0 = no byte bound), "
                f"got {self.serve_cache_bytes}")
        if self.serve_tenant_rate < 0:
            raise ValueError(
                f"serve_tenant_rate must be >= 0 (0 = unlimited), got "
                f"{self.serve_tenant_rate}")
        if self.serve_tenant_burst < 0:
            raise ValueError(
                f"serve_tenant_burst must be >= 0 (0 = auto), got "
                f"{self.serve_tenant_burst}")
        # parse for validation only — a bad weights spec fails at config
        # time, not at the first queue pickup
        parse_fair_weights(self.serve_fair_weights)
        if self.serve_hedge_ms < 0:
            raise ValueError(
                f"serve_hedge_ms must be >= 0 (0 = hedging off), got "
                f"{self.serve_hedge_ms}")
        if not 0.0 <= self.serve_hedge_max_ratio <= 1.0:
            raise ValueError(
                f"serve_hedge_max_ratio must be in [0, 1], got "
                f"{self.serve_hedge_max_ratio}")
        if self.serve_fleet_transport not in ("inproc", "proc"):
            raise ValueError(
                f"serve_fleet_transport must be 'inproc' or 'proc', got "
                f"{self.serve_fleet_transport!r}")
        if self.serve_scrape_timeout_ms <= 0:
            raise ValueError(
                f"serve_scrape_timeout_ms must be > 0 (every remote "
                f"scrape needs a hard deadline), got "
                f"{self.serve_scrape_timeout_ms}")
        if self.serve_scrape_interval_ms < 0:
            raise ValueError(
                f"serve_scrape_interval_ms must be >= 0 (0 = scrape "
                f"every read), got {self.serve_scrape_interval_ms}")
        if self.hier_chunk_words < 0:
            raise ValueError(
                f"hier_chunk_words must be >= 0 (0 = max_enc_steps), "
                f"got {self.hier_chunk_words}")
        if self.hier_chunk_words > self.max_enc_steps:
            raise ValueError(
                f"hier_chunk_words={self.hier_chunk_words} exceeds "
                f"max_enc_steps={self.max_enc_steps}: a chunk wider than "
                f"the encoder horizon is silently truncated at "
                f"tokenization and its cache key lies about its content")
        effective_chunk = self.hier_chunk_words or self.max_enc_steps
        if not 0 <= self.hier_overlap_words < effective_chunk:
            raise ValueError(
                f"hier_overlap_words must be in [0, chunk_words="
                f"{effective_chunk}) so the chunk stride stays >= 1, "
                f"got {self.hier_overlap_words}")
        for name in ("hier_chunk_tier", "hier_reduce_tier"):
            tier = getattr(self, name)
            if tier and tier not in SERVE_TIERS:
                raise ValueError(
                    f"{name} must be one of {SERVE_TIERS} (or '' for "
                    f"the default), got {tier!r}")
        if self.faults:
            # parse for validation only (unknown points / bad probs fail
            # here, at config time, not at the injection site)
            from textsummarization_on_flink_tpu.resilience import faultinject

            faultinject.parse(self.faults)


#: Per-request serving quality tiers, costliest first (SERVING.md
#: "Quality tiers"; ISSUE 10).  Dependency-light single source: the
#: serve layer validates request tiers against this and the decoder
#: dispatches on it.
SERVE_TIERS = ("beam", "greedy", "spec", "draft")


def derive_draft_hps(hps: "HParams") -> "HParams":
    """The draft model's HParams, derived from the full model's: the
    avg_attention family at the same hidden width (the checkpoint
    mapping requires it) with ``draft_dec_layers`` decoder layers
    (0 = the full model's count).  The ONE resolver — the decoder,
    the spec engine, the FLOPs gate, and bench all derive through
    here so no two components can disagree about the draft's shape."""
    return hps.replace(
        model_family="avg_attention",
        dec_layers=hps.draft_dec_layers or hps.dec_layers)


def resolve_draft_hidden(hps: "HParams") -> int:
    """Effective draft decoder width (draft_hidden, or hidden_dim when
    0) — the ONE resolver, shared by models/avg_attention.py's param
    shapes, __graft_entry__'s analytic FLOPs model, and bench's
    fingerprint so no two components can disagree about the draft's
    width."""
    return hps.draft_hidden or hps.hidden_dim


def resolve_spec_bounds(hps: "HParams") -> "Tuple[int, int, int]":
    """(k_min, k_start, k_max) for the speculative tier.  Non-adaptive
    jobs pin all three to spec_k; adaptive jobs get the committed
    [spec_k_min, spec_k_max] range.  The ONE resolver — the decoder's
    accept-histogram buckets, the SpecKController, and the adaptive
    engine's verify-cache width all derive through here, so a metric
    bucket can never be narrower than the k the controller may pick."""
    if not hps.spec_k_adaptive:
        return (hps.spec_k, hps.spec_k, hps.spec_k)
    return (hps.spec_k_min, hps.spec_k, hps.spec_k_max)


def parse_bucket_spec(spec: str, max_enc_steps: int) -> "List[int]":
    """Resolve ``serve_buckets`` to the ascending encoder-length bucket
    list the serve/ micro-batcher pads into (SERVING.md).

    The ONE parser: HParams.validate() and serve/batcher.py both resolve
    through this, so a spec that validates is exactly the spec that
    serves.  ``max_enc_steps`` is always the top bucket — an article is
    already truncated to it by SummaryExample.build, so every request
    fits some bucket.  Auto ("" spec): {max//4, max//2, max}, dropping
    sub-64 buckets (a tiny bucket saves little padding but costs a
    whole extra jit-cache entry); explicit specs keep every entry.
    Dependency-light (no jax/numpy) so config stays importable anywhere.
    """
    spec = (spec or "").strip()
    if not spec:
        buckets = sorted({max_enc_steps // 4, max_enc_steps // 2,
                          max_enc_steps})
        return [b for b in buckets
                if b == max_enc_steps or b >= 64]
    out = []
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        try:
            b = int(tok)
        except ValueError:
            raise ValueError(
                f"serve_buckets entry {tok!r} is not an integer") from None
        if b < 1:
            raise ValueError(f"serve_buckets entries must be >= 1, got {b}")
        if b > max_enc_steps:
            raise ValueError(
                f"serve_buckets entry {b} exceeds max_enc_steps="
                f"{max_enc_steps} (padding past the model's static "
                f"encoder budget buys nothing)")
        out.append(b)
    buckets = sorted(set(out))
    if not buckets or buckets[-1] != max_enc_steps:
        # the top bucket must cover every admissible article
        buckets.append(max_enc_steps)
    return buckets


def parse_fair_weights(spec: str) -> "Dict[str, float]":
    """Resolve ``serve_fair_weights`` to a {tenant: weight} dict
    (SERVING.md "Front door").

    The ONE parser: HParams.validate() and serve/queue.py both resolve
    through this, so a spec that validates is exactly the spec the
    weighted-fair pickup runs.  Unlisted tenants weigh 1.0 (the
    RequestQueue applies that default at pickup, not here).
    Dependency-light (no jax/numpy) so config stays importable anywhere.
    """
    spec = (spec or "").strip()
    if not spec:
        return {}
    out: Dict[str, float] = {}
    for tok in spec.split(","):
        tok = tok.strip()
        if not tok:
            continue
        if ":" not in tok:
            raise ValueError(
                f"serve_fair_weights entry {tok!r} must be tenant:weight")
        tenant, _, w = tok.rpartition(":")
        tenant = tenant.strip()
        if not tenant:
            raise ValueError(
                f"serve_fair_weights entry {tok!r} names no tenant (the "
                f"default tenant's weight is always 1.0)")
        try:
            weight = float(w)
        except ValueError:
            raise ValueError(
                f"serve_fair_weights weight {w!r} is not a number"
            ) from None
        if weight <= 0:
            raise ValueError(
                f"serve_fair_weights weight for {tenant!r} must be > 0, "
                f"got {weight}")
        out[tenant] = weight
    return out


def resolve_tenant_burst(hps: "HParams") -> int:
    """Effective per-tenant token-bucket burst depth: the explicit
    serve_tenant_burst, or ~one second of the configured rate (min 1)
    when 0 — the ONE resolver, shared by serve/frontdoor.py and the
    SLO gate so a committed isolation number runs the burst it names."""
    if hps.serve_tenant_burst:
        return hps.serve_tenant_burst
    return max(1, int(hps.serve_tenant_rate + 0.999999))


def resolve_beam_loop(kind: Optional[str] = None) -> str:
    """Resolve the decode-loop construct: 'while' (early exit once every
    article's beam finishes), 'scan' (fixed max_dec_steps trip count),
    or 'chunked' (while over TS_BEAM_CHUNK-step scan chunks — early exit
    at chunk granularity with only ceil(T/C) dynamic iterations).

    All three produce IDENTICAL results: under vmap a while_loop already
    applies masked per-article updates until the slowest article's cond
    goes false; scan merely fixes the trip count at the worst case, and
    chunked interleaves the two at chunk granularity (pinned token-exact
    by test_beam_search's tail-chunk parity suite and, on the chip, by
    chip_smoke.py's decode phase).

    TS_BEAM_LOOP=while|scan|chunked|auto; auto (the default) is chunked
    on every backend — no environment or backend probe decides it.
    The ONE resolver: decode/beam_search.py routes on it (as
    `_loop_kind`) and bench.py's row label records it.
    """
    import os

    kind = (kind or os.environ.get("TS_BEAM_LOOP", "auto")).lower()
    if kind == "auto":
        return "chunked"
    if kind not in ("while", "scan", "chunked"):
        raise ValueError(
            f"beam loop kind must be while|scan|chunked|auto, got {kind!r} "
            f"(TS_BEAM_LOOP or the loop= argument)")
    return kind


def beam_chunk_from_env() -> int:
    """Effective TS_BEAM_CHUNK for the chunked beam-decode loop.

    The SINGLE source of the 25-step default: decode/beam_search.py
    resolves the jit cache key through this, and bench.py's config
    fingerprint (which must stay importable without jax) records it —
    a drift between the two would let a measurement under one chunk
    size stand in for another.
    """
    import os

    return int(os.environ.get("TS_BEAM_CHUNK", "25"))


def resolve_serve_slots(hps: "HParams") -> int:
    """Effective continuous-mode slot count (serve_slots, or batch_size
    when 0) — the ONE resolver, shared by serve/server.py and bench.py
    so a measurement's slot count is exactly the server's."""
    return hps.serve_slots or hps.batch_size


def resolve_enc_block(hps: "HParams") -> int:
    """Effective encoder-key block length for the length-masked slot
    decode step (prefill/decode disaggregation, SERVING.md): the
    decode_enc_block HParam clamped to [1, max_enc_steps] — the ONE
    resolver, shared by the model families' blocked attention paths and
    __graft_entry__.decode_step_cost, so the measured program's block
    structure is exactly the served one's."""
    return max(1, min(int(hps.decode_enc_block), hps.max_enc_steps))


def bucket_for(buckets: "List[int]", enc_len: int) -> int:
    """Smallest bucket covering ``enc_len`` (the serve/ micro-batcher's
    routing rule, now shared with the continuous engine's prefill stage
    — ONE rule, so the two serving modes bucket identically).  Articles
    are already truncated to buckets[-1] by SummaryExample.build."""
    for b in buckets:
        if enc_len <= b:
            return b
    return buckets[-1]


def resolve_refill_chunk(hps: "HParams") -> int:
    """Effective continuous-mode chunk length: serve_refill_chunk, or
    the TS_BEAM_CHUNK default (the chunked beam loop's single source),
    clamped to [1, max_dec_steps]."""
    chunk = hps.serve_refill_chunk or beam_chunk_from_env()
    return max(1, min(int(chunk), hps.max_dec_steps))


def resolve_arena_pages(hps: "HParams", slots: int,
                        page_bytes: "Optional[int]" = None) -> int:
    """Page count of the slot arena of an engine with ``slots`` slots
    (ISSUE 20): ``serve_arena_pages`` when set, else the pages a
    ``serve_arena_mb`` HBM byte budget buys (page_bytes — one page's
    span across all pools, beam_search.paged_page_bytes — is required
    for budget mode), else ``slots x ceil(max_enc_steps / block)``: the
    arena that holds every slot at full length, where no admission can
    be blocked on pages.  The ONE resolver, shared by
    decode/decoder.SlotDecodeEngine and __graft_entry__'s cost model,
    so the priced arena is exactly the served one.  The result holds at
    least one full-length article (ceil(max_enc_steps /
    decode_enc_block) pages) — anything smaller would deadlock the
    first max-length admission rather than backpressure it."""
    b_max = -(-hps.max_enc_steps // resolve_enc_block(hps))
    if hps.serve_arena_pages > 0:
        pages = int(hps.serve_arena_pages)
    elif hps.serve_arena_mb > 0:
        if not page_bytes or page_bytes <= 0:
            raise ValueError(
                "serve_arena_mb sizing needs page_bytes "
                "(beam_search.paged_page_bytes(params, hps))")
        pages = int(hps.serve_arena_mb * (1 << 20) // page_bytes)
    else:
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        return int(slots) * b_max
    if pages < b_max:
        raise ValueError(
            f"arena of {pages} page(s) cannot hold one full-length "
            f"article ({b_max} pages of {resolve_enc_block(hps)} rows "
            f"at max_enc_steps={hps.max_enc_steps}); raise "
            f"serve_arena_pages/serve_arena_mb or decode_enc_block")
    return pages


def resolve_hier_chunk_words(hps: "HParams") -> int:
    """Effective map-pass chunk width for hierarchical summarization:
    ``hier_chunk_words``, or the full encoder horizon when 0.  The ONE
    resolver — serve/hiersum.py's chunker, the SLO gate's document
    construction, and bench's fingerprint all derive through here so no
    two components can disagree about where a chunk boundary falls
    (boundary drift would silently break the append-path cache pins)."""
    return hps.hier_chunk_words or hps.max_enc_steps


def flash_mode_from_env() -> str:
    """TS_FLASH resolved to 'on' / 'off' / 'auto' — the ONE token parser
    (models/transformer._use_flash routes on it; bench.py's fingerprint
    resolves it further to the actual kernel choice)."""
    import os

    v = os.environ.get("TS_FLASH", "auto").lower()
    if v in ("1", "on", "true", "yes"):
        return "on"
    if v in ("0", "off", "false", "no"):
        return "off"
    return "auto"
