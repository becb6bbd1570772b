"""Beam-search decode driver: checkpoint loading, decode loop, writers.

Rebuilds the reference BeamSearchDecoder
(/root/reference/src/main/python/pointer-generator/decode.py) TPU-first:
instead of one encoder `sess.run` plus ~100 single-step `sess.run`s per
article (decode.py:95-106 -> beam_search.py:118), each batch of articles is
decoded in ONE device dispatch (decode/beam_search.py), and the TF
Saver/session machinery is replaced by the npz checkpoint layer.

Preserved behavior:
  * decode-dir naming from the checkpoint name + key hps
    (`get_decode_dir_name`, decode.py:303-313);
  * single-pass mode writes pyrouge-layout reference/decoded files and runs
    ROUGE at the end (decode.py:133-147, 187-222, 268-301);
  * continuous mode periodically reloads the newest checkpoint
    (SECS_UNTIL_NEW_CKPT=60, decode.py:36,149-157) and writes the
    attention-visualizer JSON (decode.py:225-249);
  * `[STOP]`-truncation of the emitted token stream (decode.py:112-118);
  * html-escaping of <, > in outputs (`make_html_safe`, decode.py:252-255);
  * streaming results carry (uuid, article, summary, reference) rows with
    the summary sentence-split on '.' (`write_for_flink`, decode.py:159-185).
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from textsummarization_on_flink_tpu import obs
from textsummarization_on_flink_tpu.obs import profile as profile_lib
from textsummarization_on_flink_tpu.checkpoint import checkpointer as ckpt_lib
from textsummarization_on_flink_tpu.config import (
    SERVE_TIERS,
    HParams,
    bucket_for,
    derive_draft_hps,
    parse_bucket_spec,
    resolve_arena_pages,
    resolve_enc_block,
    resolve_spec_bounds,
)
from textsummarization_on_flink_tpu.data import oov as oov_lib
from textsummarization_on_flink_tpu.data.batching import Batch
from textsummarization_on_flink_tpu.data.vocab import STOP_DECODING, Vocab
from textsummarization_on_flink_tpu.decode import beam_search
from textsummarization_on_flink_tpu.decode.arena import (
    ArenaExhaustedError,
    PageArena,
)
from textsummarization_on_flink_tpu.evaluate import rouge
from textsummarization_on_flink_tpu.resilience.policy import Deadline

log = logging.getLogger(__name__)

SECS_UNTIL_NEW_CKPT = 60  # decode.py:36


def make_html_safe(s: str) -> str:
    """decode.py:252-255."""
    return s.replace("<", "&lt;").replace(">", "&gt;")


def words_to_sentences(decoded_words: List[str]) -> List[str]:
    """Split a decoded word stream into '.'-terminated sentences
    (decode.py:193-201 / write_for_flink :166-175)."""
    words = list(decoded_words)
    sents: List[str] = []
    while words:
        try:
            fst_period_idx = words.index(".")
        except ValueError:
            fst_period_idx = len(words) - 1
        sent = words[: fst_period_idx + 1]
        words = words[fst_period_idx + 1:]
        sents.append(" ".join(sent))
    return sents


def get_decode_dir_name(hps: HParams, ckpt_path: Optional[str]) -> str:
    """decode.py:303-313 naming (ckpt basename + key decode hps)."""
    if ckpt_path is not None:
        ckpt_name = "ckpt-" + os.path.basename(ckpt_path).split("-")[-1].split(".")[0]
    else:
        ckpt_name = "ckpt-none"
    return (f"decode_{ckpt_name}_{hps.max_enc_steps}maxenc_"
            f"{hps.beam_size}beam_{hps.min_dec_steps}mindec_"
            f"{hps.max_dec_steps}maxdec")


class DecodedResult:
    """One article's decode output (the streaming-row payload)."""

    def __init__(self, uuid: str, article: str, decoded_words: List[str],
                 reference: str, abstract_sents: List[str],
                 attn_dists: Optional[np.ndarray] = None,
                 p_gens: Optional[np.ndarray] = None,
                 degraded: bool = False, tier: str = "beam",
                 params_fingerprint: str = "",
                 avg_log_prob: float = 0.0):
        self.uuid = uuid
        self.article = article
        self.decoded_words = decoded_words
        self.reference = reference
        self.abstract_sents = abstract_sents
        self.attn_dists = attn_dists
        self.p_gens = p_gens
        # True when the decode deadline forced beam search down to greedy
        # (RESILIENCE.md graceful degradation; hps.decode_deadline_secs)
        self.degraded = degraded
        # the quality tier that produced this result (SERVING.md
        # "Quality tiers": beam|greedy|spec|draft)
        self.tier = tier
        # fingerprint of the params snapshot that DECODED this result
        # (ISSUE 14): the summary cache files entries under it, so a
        # result produced just before a hot-swap lands under the
        # snapshot that made it, never the one that replaced it ("" =
        # producer without the surface: stubs, sim engines)
        self.params_fingerprint = params_fingerprint
        # the winning hypothesis' length-normalized log probability
        # (BeamSearchOutput.avg_log_prob): what a cross-engine or
        # cross-device comparison needs to tell a near-tie flipped by
        # reduced-precision matmuls from a wrong search (chip_smoke.py)
        self.avg_log_prob = avg_log_prob

    @property
    def decoded_sents(self) -> List[str]:
        return [make_html_safe(s) for s in words_to_sentences(self.decoded_words)]

    @property
    def summary(self) -> str:
        return " ".join(self.decoded_sents)

    def as_row(self) -> Tuple[str, str, str, str]:
        """(uuid, article, summary, reference) — the write_for_flink row
        (flink_writer.py:22-34 field set)."""
        return (self.uuid, self.article, self.summary, self.reference)


class BeamSearchDecoder:
    """Decode loop driver (decode.py:42-157).

    params_source: either a static params pytree (`params=`) or a train
    dir to load checkpoints from (`train_dir=`, with load_ckpt retry —
    util.py:29-41 — and 60s reloads in continuous mode).
    """

    def __init__(self, hps: HParams, vocab: Vocab, batcher: Any,
                 params: Optional[Any] = None,
                 train_dir: Optional[str] = None,
                 decode_root: Optional[str] = None,
                 max_ckpt_retries: Optional[int] = None,
                 draft_params: Optional[Any] = None):
        if params is None and train_dir is None:
            raise ValueError("need params or train_dir")
        self._hps = hps
        self._vocab = vocab
        self._batcher = batcher
        self._train_dir = train_dir
        self._max_ckpt_retries = max_ckpt_retries
        # guards the (params, ckpt_path) PAIR: continuous-mode reloads
        # (and the serve/ hot-swap) replace both together, and a
        # concurrent decode_batch must never observe a half-swapped
        # state (new params with the old checkpoint name, or vice versa)
        self._params_lock = threading.Lock()
        self._ckpt_path: Optional[str] = None
        # (params object, its content fingerprint) — the
        # params_fingerprint property's one-sha-per-swap memo
        self._fp_cache: Optional[Tuple[Any, str]] = None
        # observability (`decode/` namespace, OBSERVABILITY.md):
        # per-request latency percentiles, finished beams, token volume
        # (tokens/sec = decode/tokens_total over decode/busy_seconds_total),
        # and continuous-mode checkpoint reloads
        self._obs = obs.registry_for(hps)
        self._m_latency = self._obs.histogram("decode/request_latency_seconds")
        self._c_requests = self._obs.counter("decode/requests_total")
        self._c_beams = self._obs.counter("decode/beams_finished_total")
        self._c_tokens = self._obs.counter("decode/tokens_total")
        self._c_busy = self._obs.counter("decode/busy_seconds_total")
        self._c_reloads = self._obs.counter("decode/ckpt_reloads_total")
        # resilience (RESILIENCE.md): per-request Deadline + graceful
        # degradation.  `_beam_secs` is an EMA of observed FULL-BEAM
        # dispatch latency; once it exists and a request's remaining
        # budget cannot cover it, the dispatch runs greedy (beam_size=1)
        # and its results are tagged degraded=True.
        self._c_degraded = self._obs.counter(
            "resilience/decode_degraded_total")
        self._g_beam_est = self._obs.gauge(
            "resilience/decode_beam_latency_est_seconds")
        self._beam_secs: Optional[float] = None
        # the FIRST full-beam dispatch carries the jit compile (seconds
        # to minutes); recording it would lock every later request into
        # greedy, so the EMA only starts at the second full-beam dispatch
        self._beam_warm = False
        # ---- speculative tier (SERVING.md "Quality tiers"; ISSUE 10) ----
        # draft params ride the SAME lock as the full pair: with
        # spec_draft="map" a checkpoint hot-swap re-derives the draft,
        # and a spec dispatch must never pair old draft with new full
        self._draft_params = draft_params
        # accept-length histogram buckets span the FULL committed k
        # range (0..spec_k_max via resolve_spec_bounds): under the
        # adaptive controller, cycles run at k up to spec_k_max, and
        # spec_k-sized buckets would pile every longer acceptance into
        # the overflow bin (the ISSUE-12 satellite fix — same shape of
        # fix as PR 11's serve/prefill_bucket_len)
        _, _, spec_k_max = resolve_spec_bounds(hps)
        self._h_accept = self._obs.histogram(
            "decode/spec_accept_len",
            buckets=[float(i) for i in range(0, spec_k_max + 1)])
        self._c_spec_cycles = self._obs.counter("decode/spec_cycles_total")
        self._c_spec_drafted = self._obs.counter(
            "decode/spec_draft_tokens_total")
        self._c_spec_accepted = self._obs.counter(
            "decode/spec_accepted_tokens_total")
        # acceptance-adaptive spec_k (ISSUE 12): ONE controller per
        # decoder — it adapts k between cycles inside a dispatch and
        # carries the learned acceptance estimate across requests; its
        # current pick is exported as a gauge.  Mutated only on the
        # dispatch path (the serve layer runs one dispatch thread).
        self._spec_ctl = None
        self._g_spec_k = self._obs.gauge("decode/spec_k_current")
        # documented semantics (OBSERVABILITY.md): the gauge reads
        # spec_k when non-adaptive, the controller's live pick otherwise
        self._g_spec_k.set(float(hps.spec_k))
        if getattr(hps, "spec_k_adaptive", False):
            from textsummarization_on_flink_tpu.decode import speculative

            self._spec_ctl = speculative.SpecKController.from_hps(hps)
            self._g_spec_k.set(float(self._spec_ctl.k))
        self._params = params
        if params is None:
            self._load_params()
        if self._draft_params is None and hps.spec_draft:
            from textsummarization_on_flink_tpu.models import avg_attention

            self._draft_params = avg_attention.make_draft_params(
                hps, self._params, seed=hps.seed)

        self._sharded_search = None
        self._mesh_plan = None
        if hps.dp * hps.tp * hps.sp > 1:
            # multi-chip serving: articles shard over dp, beams chip-local
            from textsummarization_on_flink_tpu.parallel import mesh as mesh_lib

            mesh_lib.validate_divisibility(hps, self._params)
            self._mesh_plan = mesh_lib.make_mesh(hps)
            self._sharded_search = mesh_lib.make_sharded_beam_search(
                self._mesh_plan, params=self._params)

        root = decode_root or os.path.join(hps.log_root or ".",
                                           hps.exp_name or "exp")
        if hps.single_pass:
            self._decode_dir = os.path.join(
                root, get_decode_dir_name(hps, self._ckpt_path))
            if os.path.exists(self._decode_dir):
                raise FileExistsError(
                    f"single_pass decode directory {self._decode_dir} should "
                    "not already exist")  # decode.py:70-71
        else:
            self._decode_dir = os.path.join(root, "decode")
        os.makedirs(self._decode_dir, exist_ok=True)
        self._rouge_ref_dir = os.path.join(self._decode_dir, "reference")
        self._rouge_dec_dir = os.path.join(self._decode_dir, "decoded")
        if hps.single_pass:
            os.makedirs(self._rouge_ref_dir, exist_ok=True)
            os.makedirs(self._rouge_dec_dir, exist_ok=True)

    # -- checkpoint handling --
    def _params_snapshot(self) -> Tuple[Any, Optional[str]]:
        """Atomic read of the (params, ckpt_path) pair — the one sanctioned
        way for a dispatch to pick up weights while reloads may run."""
        with self._params_lock:
            return self._params, self._ckpt_path

    @property
    def params_fingerprint(self) -> str:
        """Content fingerprint of the ACTIVE ``_params_snapshot``
        (``checkpoint.checkpointer.content_fingerprint`` — the one
        scheme the distill teacher sidecar also uses) — the serve
        layer's cache key and /healthz surface (SERVING.md "Front
        door").  Cached per swapped-in params OBJECT: the sha runs once
        per checkpoint hot-swap, not per request (the cache tuple holds
        the source tree, so object identity can never false-hit on a
        recycled address)."""
        params, _ = self._params_snapshot()
        cached = self._fp_cache
        if cached is not None and cached[0] is params:
            return cached[1]
        fp = ckpt_lib.content_fingerprint(params)
        self._fp_cache = (params, fp)
        return fp

    def _load_params(self) -> None:
        # load + decode OUTSIDE the lock (seconds of IO must not stall
        # concurrent dispatches); only the pointer swap is locked
        path, flat = ckpt_lib.load_ckpt(self._train_dir,
                                        max_retries=self._max_ckpt_retries)
        state = ckpt_lib.arrays_to_state(flat)
        draft = None
        if self._hps.spec_draft == "map":
            # the mapped draft is a VIEW of the full checkpoint: derive
            # it from the same params the swap installs (outside the
            # lock, like the load), so spec dispatches never pair a
            # fresh full model with a stale draft
            from textsummarization_on_flink_tpu.models import avg_attention

            draft = avg_attention.make_draft_params(
                self._hps, state.params, seed=self._hps.seed)
        with self._params_lock:
            self._params = state.params
            self._ckpt_path = path
            if draft is not None:
                self._draft_params = draft
        log.info("decoder loaded checkpoint %s", path)

    def maybe_reload_checkpoint(self, last_load: float) -> float:
        """Continuous-serving checkpoint refresh (decode.py:149-157).

        ``last_load`` is a ``time.monotonic()`` reference: the 60s reload
        cadence is a duration, and a wall-clock jump (NTP slew, suspend)
        must neither storm reloads nor starve them (TS003).

        Thread-safe hot-swap (ISSUE 4 satellite): the (params,
        ckpt_path) pair swaps under ``_params_lock``, so a concurrent
        ``decode_batch`` (the serve/ dispatch thread, or any
        out-of-band caller) sees either the old pair or the new one —
        never a half-swap.  Each swap bumps
        ``decode/ckpt_reloads_total``.  The sharded (mesh) search closes
        over its initial params and does NOT hot-swap."""
        if self._train_dir is None:
            return last_load
        if time.monotonic() - last_load < SECS_UNTIL_NEW_CKPT:
            return last_load
        latest = ckpt_lib.latest_checkpoint(self._train_dir)
        _, current = self._params_snapshot()
        if latest is not None and latest != current:
            log.info("Decoder has been decoding for %.0f seconds; loading "
                     "new checkpoint", time.monotonic() - last_load)
            self._load_params()
            self._c_reloads.inc()
        return time.monotonic()

    # -- decoding --
    def should_degrade(self, deadline: Deadline) -> bool:
        """True when the remaining request budget cannot cover a
        full-beam dispatch (RESILIENCE.md degradation contract) — the
        serve layer's per-REQUEST re-tiering predicate (SERVING.md
        "Quality tiers").

        Requires a latency estimate from a completed full-beam dispatch
        AFTER the compile-inclusive first one — early requests are never
        degraded.  Single-host path
        only: the sharded search is jit-built once for the mesh plan and
        cannot swap beam width per request."""
        return (deadline.bounded
                and self._sharded_search is None
                and self._hps.beam_size > 1
                and self._beam_secs is not None
                and deadline.remaining() < self._beam_secs)

    _should_degrade = should_degrade  # historical internal name

    @property
    def has_draft(self) -> bool:
        """Whether the spec/draft tiers are servable (a draft model is
        configured — mapped, fresh, or injected)."""
        return self._draft_params is not None

    @property
    def sharded(self) -> bool:
        """True on a dp/tp mesh: the sharded search is jit-built once
        for the mesh plan, so only the beam tier is servable (the serve
        layer rejects other tiers at submit)."""
        return self._sharded_search is not None

    def _spec_snapshot(self) -> Tuple[Any, Any]:
        """Atomic (full params, draft params) read — the spec tier's
        analogue of ``_params_snapshot`` (a hot-swap replaces both under
        the same lock, so a dispatch never pairs mismatched models)."""
        with self._params_lock:
            return self._params, self._draft_params

    def decode_batch(self, batch: Batch,
                     deadline: Optional[Deadline] = None,
                     tier: Optional[str] = None) -> List[DecodedResult]:
        """One device dispatch for the whole batch; returns one result per
        REAL input row (``batch.real_mask``).  Padding rows — beam
        repetition in decode 'repeat' mode (batcher.py:344-347) and
        trickle/tail padding — are tagged by the batcher and dropped here;
        two legitimately identical input rows each get a result, matching
        the reference's one-result-per-record contract (decode.py:159-185).

        Resilience: every call carries a Deadline — the caller's, or one
        built from ``hps.decode_deadline_secs`` (0 = unbounded, never
        degrade).  When the budget is short of the full-beam latency
        estimate the dispatch degrades to greedy (beam_size=1); results
        are tagged ``degraded=True`` and counted in
        ``resilience/decode_degraded_total``.

        Quality tiers (SERVING.md "Quality tiers"; ISSUE 10): an
        explicit ``tier`` (beam|greedy|spec|draft) dispatches exactly
        that tier — the serve layer already made the per-request
        degradation decision, so the internal deadline ladder is
        skipped.  ``tier=None`` keeps the historical behavior (beam,
        degrading to greedy under deadline pressure)."""
        if deadline is None:
            deadline = Deadline.after(
                getattr(self._hps, "decode_deadline_secs", 0.0))
        explicit = tier is not None
        if explicit:
            if tier not in SERVE_TIERS:
                raise ValueError(
                    f"tier must be one of {SERVE_TIERS}, got {tier!r}")
            degraded = False
            eff_tier = tier
        else:
            degraded = self.should_degrade(deadline)
            eff_tier = "greedy" if degraded else "beam"
        t0 = time.perf_counter()
        with obs.spans.span(self._obs, "decode/batch", tier=eff_tier):
            results = self._decode_batch_inner(batch, tier=eff_tier)
        dt = time.perf_counter() - t0
        if degraded:
            for res in results:
                res.degraded = True
            self._c_degraded.inc(len(results))
            log.warning("decode deadline short of full-beam estimate "
                        "(%.3fs remaining < %.3fs est); degraded %d "
                        "result(s) to greedy", deadline.remaining(),
                        self._beam_secs, len(results))
        elif eff_tier == "beam":
            if not self._beam_warm:
                self._beam_warm = True  # compile-inclusive sample: discard
            else:
                # EMA of full-beam dispatch latency (greedy/spec/draft
                # dispatches and compile times must not poison the
                # estimate the degradation ladder keys on)
                self._beam_secs = (dt if self._beam_secs is None
                                   else 0.7 * self._beam_secs + 0.3 * dt)
                self._g_beam_est.set(self._beam_secs)
        self._c_busy.inc(dt)
        # requests in a batch share one dispatch: the batch wall time IS
        # each request's observed latency
        for res in results:
            self._m_latency.observe(dt)
            self._c_tokens.inc(len(res.decoded_words))
        self._c_requests.inc(len(results))
        self._c_beams.inc(len(results))
        return results

    def _decode_batch_inner(self, batch: Batch,
                            tier: str = "beam") -> List[DecodedResult]:
        # one atomic params read per dispatch: a checkpoint hot-swap
        # landing mid-batch affects the NEXT dispatch, never this one
        params, _ = self._params_snapshot()
        if self._sharded_search is not None:
            if tier != "beam":
                raise ValueError(
                    f"sharded (mesh) serving supports the beam tier only "
                    f"(the search is jit-built once for the mesh plan); "
                    f"got tier={tier!r}")
            from textsummarization_on_flink_tpu.parallel import mesh as mesh_lib

            enc_only = {k: v for k, v in batch.as_arrays().items()
                        if k.startswith("enc_")}
            raw = self._sharded_search(
                params, mesh_lib.shard_batch(self._mesh_plan, enc_only))
            out = beam_search.BeamSearchOutput(
                *[np.asarray(x) for x in raw])
        elif tier == "spec":
            from textsummarization_on_flink_tpu.decode import speculative

            full, draft = self._spec_snapshot()
            if draft is None:
                raise ValueError(
                    "spec tier needs a draft model: set hps.spec_draft "
                    "('map'/'fresh') or pass draft_params=")
            real = np.asarray(batch.real_mask, dtype=bool)
            out = speculative.run_spec_decode(full, draft, self._hps,
                                              batch.as_arrays(),
                                              controller=self._spec_ctl,
                                              real_mask=real)
            if self._spec_ctl is not None:
                self._g_spec_k.set(float(self._spec_ctl.k))
            self._c_spec_cycles.inc(int(out.cycles[real].sum()))
            self._c_spec_drafted.inc(int(out.drafted[real].sum()))
            self._c_spec_accepted.inc(int(out.accepted[real].sum()))
            # accept_hist already holds per-length cycle counts: fold
            # the batch once and record O(spec_k) weighted observes,
            # not one lock acquisition per verify cycle
            per_len = out.accept_hist[real].sum(axis=0)
            for a, count in enumerate(per_len):
                self._h_accept.observe(float(a), n=int(count))
        elif tier == "draft":
            _, draft = self._spec_snapshot()
            if draft is None:
                raise ValueError(
                    "draft tier needs a draft model: set hps.spec_draft "
                    "('map'/'fresh') or pass draft_params=")
            dhps = derive_draft_hps(self._hps).replace(beam_size=1,
                                                       mode="decode")
            out = beam_search.run_beam_search(draft, dhps,
                                              batch.as_arrays())
        else:
            hps = (self._hps.replace(beam_size=1) if tier == "greedy"
                   else self._hps)
            out = beam_search.run_beam_search(params, hps,
                                              batch.as_arrays())
        results: List[DecodedResult] = []
        for b in range(len(batch.original_articles)):
            if not batch.real_mask[b]:
                continue
            results.append(self._make_result(
                out.tokens[b], int(out.length[b]), out.attn_dists[b],
                out.p_gens[b], avg_log_prob=float(out.avg_log_prob[b]),
                uuid=batch.uuids[b],
                article=batch.original_articles[b],
                reference=batch.references[b],
                abstract_sents=batch.original_abstracts_sents[b],
                art_oovs=batch.art_oovs[b], tier=tier))
        return results

    def _make_result(self, tokens, length: int, attn_dists, p_gens, *,
                     uuid: str, article: str, reference: str,
                     abstract_sents: List[str],
                     art_oovs: List[str], tier: str = "beam",
                     avg_log_prob: float = 0.0) -> DecodedResult:
        """One article's raw beam output -> DecodedResult: START strip,
        id->word mapping through the article's OOVs, [STOP] truncation
        (decode.py:112-118).  Shared by the batch path and the slot
        engine so the two serving modes emit identical rows."""
        output_ids = [int(t) for t in tokens[1:length]]  # strip START
        decoded_words = oov_lib.outputids2words(
            output_ids, self._vocab, art_oovs)
        try:
            fst_stop_idx = decoded_words.index(STOP_DECODING)
            decoded_words = decoded_words[:fst_stop_idx]
        except ValueError:
            pass
        return DecodedResult(
            uuid=uuid,
            article=article,
            decoded_words=decoded_words,
            reference=reference,
            abstract_sents=abstract_sents,
            attn_dists=attn_dists[: max(len(decoded_words), 1)],
            p_gens=p_gens[: max(len(decoded_words), 1)],
            tier=tier, avg_log_prob=avg_log_prob,
            # the fingerprint memo is keyed on the snapshot object, so
            # this is a dict read per result, not a sha — and a swap
            # landing mid-batch at worst stamps the NEW snapshot on a
            # result the old one decoded, which only costs a cache miss
            params_fingerprint=self.params_fingerprint)

    def slot_engine(self, slots: int, chunk: int) -> "SlotDecodeEngine":
        """The continuous-batching engine over this decoder's params
        (SERVING.md 'Continuous batching'): `slots` resident articles
        decoded in `chunk`-step pieces with in-flight refill."""
        return SlotDecodeEngine(self, slots, chunk)

    def decode(self, with_rouge: bool = True,
               result_sink: Optional[Callable[[DecodedResult], None]] = None,
               max_batches: int = 0, log_results: bool = True,
               ) -> Optional[Dict[str, Dict[str, float]]]:
        """The main loop (decode.py:131-157).

        single_pass: decode everything once, write rouge files, then
        evaluate (when with_rouge).  Otherwise: decode forever (or until the
        batcher ends / max_batches), pushing results to `result_sink`
        immediately — no buffering, the Issue-6 fix — reloading fresh
        checkpoints every 60s.

        log_results=False suppresses the continuous-mode article/summary
        INFO logging and the per-result attn_vis_data.json rewrite — the
        serving path (pipeline transform) wants results through the sink
        only, not an unbounded per-record disk write.
        """
        t_last = time.monotonic()
        counter = 0
        n_batches = 0
        while True:
            batch = self._batcher.next_batch()
            if batch is None:
                if self._hps.single_pass:
                    log.info("Decoder has finished reading dataset for "
                             "single_pass.")
                    break
                log.info("batcher exhausted; stopping decode loop")
                break
            t0 = time.monotonic()
            results = self.decode_batch(batch)
            log.info("decoded batch of %d article(s) in %.3f s",
                     len(results), time.monotonic() - t0)
            for res in results:
                if self._hps.single_pass:
                    self.write_for_rouge(res, counter)
                    counter += 1
                elif log_results:
                    log.info("ARTICLE: %s", res.article)
                    log.info("GENERATED SUMMARY: %s", res.summary)
                    self.write_for_attnvis(res)
                if result_sink is not None:
                    result_sink(res)  # immediate flush
            n_batches += 1
            if max_batches and n_batches >= max_batches:
                break
            if not self._hps.single_pass:
                t_last = self.maybe_reload_checkpoint(t_last)
        if self._hps.single_pass and with_rouge and counter > 0:
            log.info("Output has been saved in %s and %s. Now starting "
                     "ROUGE eval...", self._rouge_ref_dir, self._rouge_dec_dir)
            results_dict = rouge.rouge_eval(self._rouge_ref_dir,
                                            self._rouge_dec_dir)
            rouge.rouge_log(results_dict, self._decode_dir)
            return results_dict
        return None

    # -- writers --
    def write_for_rouge(self, res: DecodedResult, ex_index: int) -> None:
        """pyrouge file layout (decode.py:187-222)."""
        decoded_sents = res.decoded_sents
        reference_sents = [make_html_safe(s) for s in res.abstract_sents]
        ref_file = os.path.join(self._rouge_ref_dir,
                                f"{ex_index:06d}_reference.txt")
        decoded_file = os.path.join(self._rouge_dec_dir,
                                    f"{ex_index:06d}_decoded.txt")
        with open(ref_file, "w", encoding="utf-8") as f:
            for idx, sent in enumerate(reference_sents):
                f.write(sent + ("\n" if idx < len(reference_sents) - 1 else ""))
        with open(decoded_file, "w", encoding="utf-8") as f:
            for idx, sent in enumerate(decoded_sents):
                f.write(sent + ("\n" if idx < len(decoded_sents) - 1 else ""))
        log.info("Wrote example %i to file", ex_index)

    def write_for_attnvis(self, res: DecodedResult) -> None:
        """attn_vis JSON (decode.py:225-249 field layout)."""
        article_lst = res.article.split()
        to_write = {
            "article_lst": [make_html_safe(t) for t in article_lst],
            "decoded_lst": [make_html_safe(t) for t in res.decoded_words],
            "abstract_str": make_html_safe(" ".join(res.abstract_sents)),
            "attn_dists": (res.attn_dists[:, : len(article_lst)].tolist()
                           if res.attn_dists is not None else []),
        }
        if self._hps.pointer_gen and res.p_gens is not None:
            to_write["p_gens"] = res.p_gens.tolist()
        output_fname = os.path.join(self._decode_dir, "attn_vis_data.json")
        with open(output_fname, "w", encoding="utf-8") as f:
            json.dump(to_write, f)
        log.info("Wrote visualization data to %s", output_fname)


class PrefilledArticle(NamedTuple):
    """Host-side handle for one article through the PREFILL stage
    (ISSUE 11): the device-resident PrefillState (encoder +
    cross-attention cache at the article's bucket, padded to the
    resident width) plus the request bookkeeping pack needs."""

    example: Any  # the SummaryExample (uuid/reference/OOVs travel here)
    state: Any  # beam_search.PrefillState
    bucket: int  # the encoder bucket the prefill compiled/ran at


class SlotDecodeEngine:
    """Host driver of beam_search's persistent slot kernels (ISSUE 6),
    disaggregated into a bucketed prefill stage and a length-masked
    decode stage (ISSUE 11).

    Owns the resident state (per-slot leaves plus the page pools its
    PageArena hands out, ISSUE 20), the page table and the per-slot
    activity mask; the scheduler above it
    (serve/batcher.ContinuousBatcher) owns request bookkeeping.
    Single-threaded by design — the one
    continuous-dispatch thread calls prefill/pack/step/unpack_many; the
    host syncs are reading the `finished` mask in step() and ONE read of
    every retiring slot's output a harvest in unpack_many().

    Shape discipline: the RESIDENT state keeps one shape
    (``hps.max_enc_steps`` wide — that is what makes slot recycling
    shape-stable), so the decode kernels warm exactly four compiles
    (init/pack/step/unpack) with slot index, occupancy, valid lengths
    and page-table rows all traced.  The COST no longer follows the
    shape: prefill runs the encoder at the article's micro-batcher bucket
    (``serve_buckets`` — one prefill compile per bucket), and each
    decode chunk's cross-attention is bounded by the longest active
    resident's true length (beam_search.step_slots_jit).  Compile
    activity stays visible in the existing
    ``decode/compile_cache_*_total`` counters.

    Checkpoint hot-swap: each kernel call reads the decoder's
    ``_params_snapshot()``, so a between-batch reload lands at the NEXT
    chunk boundary — resident articles finish under the new params
    (documented in SERVING.md; same shapes, so no recompile).

    Multi-chip serving (ISSUE 8): on a dp x tp mesh the resident
    [slots, ...] leaves shard over dp, the page pools replicate, and
    params tp-shard, all against
    the sharding registry (parallel/sharding.py) — the same layout
    story as training and the micro-batch sharded search.  Slots must
    divide by dp.  The kernels themselves are unchanged: sharded inputs
    compile to a sharded program, and the engine re-pins the state to
    the registry specs after each step so GSPMD's output layout can
    never drift from the registry's.
    """

    def __init__(self, decoder: BeamSearchDecoder, slots: int, chunk: int):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if chunk < 1:
            raise ValueError(f"refill chunk must be >= 1, got {chunk}")
        self._dec = decoder
        self._hps = decoder._hps
        self.slots = slots
        self.chunk = min(chunk, self._hps.max_dec_steps)
        self._t_enc = self._hps.max_enc_steps
        self._hps1 = self._hps.replace(batch_size=1)
        # prefill stage buckets — the micro-batcher's exact list (ONE
        # parser, config.parse_bucket_spec), so the two serving modes
        # route articles to identical encoder shapes
        self._buckets = parse_bucket_spec(self._hps.serve_buckets,
                                          self._hps.max_enc_steps)
        self._state = None  # lazy: first pack pays the init compile
        self._active = np.zeros(slots, dtype=bool)
        self._obs = obs.registry_for(self._hps)
        # ---- the page arena (ISSUE 20) ----
        # enc-axis resident leaves pool into a shared
        # decode_enc_block-row page arena, each admission allocates
        # ceil(true_len/block) pages, and the per-slot page-table rows
        # ride into the kernels as traced DATA.  With no arena option
        # set the arena holds every slot at full length and no
        # admission waits for pages (config.resolve_arena_pages).
        self._block = resolve_enc_block(self._hps)
        self._b_max = -(-self._hps.max_enc_steps // self._block)
        self._page_bytes = beam_search.paged_page_bytes(
            decoder._params_snapshot()[0], self._hps)
        self._arena_pages = resolve_arena_pages(self._hps, slots,
                                                self._page_bytes)
        self._arena = PageArena(self._arena_pages)
        # scratch-filled page table; row i mirrors slot i's allocation
        self._table = np.full((slots, self._b_max), self._arena_pages,
                              np.int32)
        self._page_rows: Dict[int, np.ndarray] = {}
        # commit the compile-once warm set to the compile ledger
        # (obs/profile.py, ISSUE 16): exactly one compile per decode
        # kernel (idx/occupancy/valid-lengths all traced) and one
        # prefill per serve bucket — growth beyond these budgets is a
        # compile storm (flight dump + /alerts), not just a failed test
        self._prof = profile_lib.profiler_for(self._obs)
        for kernel in ("decode/init_slots_jit", "decode/pack_slot_jit",
                       "decode/step_slots_jit", "decode/unpack_slot_jit"):
            self._prof.set_compile_budget(kernel, 1)
        self._prof.set_compile_budget("decode/prefill_jit",
                                      len(self._buckets))
        self._priced_buckets: set = set()
        if getattr(self._hps, "profile_analytic", False):
            # price the slot chunk ONCE for the divergence sentinel
            # (the helper AOT-compiles; profile.py runs it off-thread)
            chunk_hps, chunk = self._hps, self.chunk
            self._prof.register_cost(
                "serve/dispatch", f"slot_chunk{chunk}",
                lambda: __import__("__graft_entry__").decode_step_cost(
                    chunk_hps, path="slot", chunk=chunk))
        self._registry = None
        # (source params tree, its registry-placed copy): holding the
        # source object keeps its id live, so the identity check below
        # can never false-hit on a recycled address after a hot-swap
        self._placed_params: Optional[Tuple[Any, Any]] = None
        hps = self._hps
        if hps.dp * hps.tp * hps.sp > 1:
            if slots % hps.dp != 0:
                raise ValueError(
                    f"continuous serving shards resident slots over dp: "
                    f"dp={hps.dp} must divide serve slots={slots}")
            # the decoder already built the mesh plan under the same
            # condition — engine and micro-batch search share ONE
            # mesh/registry by construction
            self._registry = decoder._mesh_plan.registry

    @property
    def params_fingerprint(self) -> str:
        """The owning decoder's active-params fingerprint — the
        continuous path's cache-key surface (one decoder, one
        fingerprint, both serve modes; SERVING.md "Front door")."""
        return self._dec.params_fingerprint

    def _params(self):
        """The decoder's params snapshot, placed against the registry's
        param specs on a mesh (cached per swapped-in params object, so
        a checkpoint hot-swap re-places once, not per chunk)."""
        params, _ = self._dec._params_snapshot()
        if self._registry is None:
            return params
        if self._placed_params is None or self._placed_params[0] is not params:
            self._placed_params = (params,
                                   self._registry.shard_params(params))
        return self._placed_params[1]

    def _pin_state(self, state):
        """Pin the resident state to the registry's slots-over-dp specs
        (a no-op transfer when the layout already matches)."""
        if self._registry is None:
            return state
        import jax

        reg = self._registry
        return jax.device_put(
            state, reg.shardings(reg.slot_state_specs(state)))

    def _jitted(self, site, fn, *args, key="", phase=None, **kw):
        """Run a slot kernel through the shared compile ledger
        (obs/profile.py, ISSUE 16): the jit-cache hit/miss telemetry
        this method used to hand-roll, plus per-site compile events so
        'no per-request recompiles' is runtime-monitored — growth past
        the committed warm-set budget is a compile storm.  `phase`
        brackets the call as that ``Profiler.phase``."""
        return profile_lib.compiled_call(self._obs, site, fn, *args,
                                         key=key, phase=phase, **kw)

    def _ensure_state(self, params) -> None:
        if self._state is not None:
            return
        zero = {
            "enc_batch": np.zeros((self.slots, self._t_enc), np.int32),
            "enc_lens": np.zeros((self.slots,), np.int32),
            "enc_padding_mask": np.zeros((self.slots, self._t_enc),
                                         np.float32),
            "enc_batch_extend_vocab": np.zeros((self.slots, self._t_enc),
                                               np.int32),
        }
        if self._registry is not None:
            import jax

            reg = self._registry
            specs = reg.slot_batch_specs()
            zero = {k: jax.device_put(v, reg.named(specs[k]))
                    for k, v in zero.items()}
        self._state = self._pin_state(
            self._jitted("decode/init_slots_jit",
                         beam_search.init_slots_jit, params,
                         self._hps, zero, self._arena_pages))

    def _register_prefill_cost(self, bucket: int) -> None:
        """Queue analytic pricing of one prefill bucket for the
        divergence sentinel (first use per bucket; gated on
        hps.profile_analytic because prefill_cost AOT-compiles)."""
        if not getattr(self._hps, "profile_analytic", False) \
                or bucket in self._priced_buckets:
            return
        self._priced_buckets.add(bucket)
        hps = self._hps
        self._prof.register_cost(
            "serve/prefill", bucket,
            lambda: __import__("__graft_entry__").prefill_cost(hps, bucket))

    def prefill(self, example) -> PrefilledArticle:
        """The PREFILL stage for one SummaryExample (ISSUE 11): encoder
        + cross-attention cache at the article's bucket shape — one
        prefill_jit compile per bucket, cost scaling with the bucket —
        returning the padded, valid-length-stamped handle pack()
        scatters into a slot.  Safe to run while other articles are
        resident (the scheduler overlaps prefill with decode ticks).
        Its host work is two child phases: `decode/prefill/batch` (the
        article's arrays) and `decode/prefill/dispatch`."""
        params = self._params()
        bucket = bucket_for(self._buckets, example.enc_len)
        with self._prof.phase("decode/prefill/batch"):
            batch = Batch([example], self._hps1, self._dec._vocab,
                          enc_steps=bucket)
            arrays = {k: v for k, v in batch.as_arrays().items()
                      if k.startswith("enc_")}
        self._register_prefill_cost(bucket)
        pre = self._jitted("decode/prefill_jit", beam_search.prefill_jit,
                           params, self._hps, arrays, key=bucket,
                           phase="decode/prefill/dispatch")
        if self._registry is not None:
            # the placement on a mesh: inside serve/prefill, in neither
            # child (the dispatch child is compiled_call's own bracket)
            import jax

            reg = self._registry
            pre = jax.device_put(
                pre, reg.shardings(reg.prefill_state_specs(pre)))
        return PrefilledArticle(example=example, state=pre, bucket=bucket)

    def pages_needed(self, item) -> int:
        """Arena pages one admission consumes: ceil(true_len / block),
        read from the HOST-side example length (never the device
        array — pack is a TS002 hot path)."""
        enc_len = min(int(item.example.enc_len if isinstance(
            item, PrefilledArticle) else item.enc_len),
            self._hps.max_enc_steps)
        return max(1, -(-enc_len // self._block))

    def free_pages(self) -> int:
        """Free arena pages (for the batcher's admit-by-free-pages
        check)."""
        return self._arena.free_pages

    def arena_stats(self) -> Dict[str, float]:
        """Arena occupancy snapshot for the serve metrics/bench
        evidence fields.  Pure host counters — no device sync."""
        a = self._arena
        return {"capacity": a.capacity, "free": a.free_pages,
                "in_use": a.pages_in_use, "fill": a.fill}

    def resident_bytes_per_slot(self) -> float:
        """Mean resident HBM bytes one resident actually consumes —
        the ISSUE 20 evidence figure: the per-slot share of the
        non-pooled leaves plus the IN-USE pages' bytes averaged over
        current residents — array metadata and host counters only, no
        sync."""
        if self._state is None:
            return 0.0
        import jax

        leaves = jax.tree_util.tree_leaves(self._state)
        total = float(sum(x.nbytes for x in leaves))
        pools = list(self._state.enc_pages) + [self._state.ext_pool,
                                               self._state.attn_pool]
        fixed = total - float(sum(x.nbytes for x in pools))
        n_active = max(1, int(self._active.sum()))
        return (fixed / self.slots
                + self._arena.pages_in_use * self._page_bytes / n_active)

    def pack(self, idx: int, item) -> None:
        """Admit one prefilled article (or a raw SummaryExample, which
        is prefilled inline) into slot `idx` (must be free).

        Allocates the admission's pages first — a typed
        ArenaExhaustedError propagates to the batcher BEFORE any device
        state changes (requeue, never a wrong decode), and a pack
        failure after allocation frees the pages (no leak)."""
        if self._active[idx]:
            raise AssertionError(f"slot {idx} is already resident")
        if not isinstance(item, PrefilledArticle):
            item = self.prefill(item)
        params = self._params()
        self._ensure_state(params)
        need = self.pages_needed(item)
        ids = self._arena.alloc(need)  # may raise ArenaExhaustedError
        row = np.full(self._b_max, self._arena_pages, np.int32)
        row[:need] = ids
        try:
            self._state = self._pin_state(
                self._jitted("decode/pack_slot_jit",
                             beam_search.pack_slot_jit, params,
                             self._hps, self._state, idx, item.state,
                             row))
        except BaseException:
            self._arena.free(ids)
            raise
        self._table[idx] = row
        self._page_rows[idx] = ids
        self._active[idx] = True

    def _step_args(self, params):
        """The slot step's arguments at the engine's current state:
        what step() runs and compiled_step() lowers."""
        return (params, self._hps, self._state, self._active, self._table,
                self.chunk)

    def compiled_step(self):
        """The ``jax.stages.Compiled`` of the slot step at the engine's
        current shapes and shardings — for ``memory_analysis()`` and for
        ``as_text()``, whose ``op_name=`` metadata maps an instruction
        of a device trace to its named scope.  Lowering the very
        arguments step() passes finds the executable step() runs in the
        compile cache; nothing runs and the compile ledger is not
        touched.  Off the hot path: call it after a measured window,
        never inside one."""
        if self._state is None:
            raise RuntimeError("the slot step has no shapes yet: nothing "
                               "was packed into this engine")
        return beam_search.step_slots_jit.lower(
            *self._step_args(self._params())).compile()

    def step(self) -> List[int]:
        """One chunk for every resident slot; returns the slot indices
        whose search finished (ready to unpack)."""
        if not self._active.any():
            return []
        params = self._params()
        self._state, finished = self._jitted(
            "decode/step_slots_jit", beam_search.step_slots_jit,
            *self._step_args(params))
        self._state = self._pin_state(self._state)
        # the one sanctioned chunk-boundary sync: the host scheduler
        # needs the finished mask to retire and refill slots.  A child
        # of the scheduler's serve/dispatch: dispatching the chunk is
        # host work, this is the wait for the device
        with self._prof.phase("serve/dispatch/wait_mask"):
            mask = np.asarray(finished)
        return [int(i) for i in np.nonzero(mask)[0]]

    def unpack(self, idx: int, example) -> DecodedResult:
        """Retire slot `idx`: finalize its hypothesis and free the slot.
        `example` is the SummaryExample packed into it (uuid/reference/
        OOV map travel with the request, not the device state).  A
        harvest of one: see unpack_many."""
        return self.unpack_many([(idx, example)])[0]

    def unpack_many(self, pairs: List[Tuple[int, Any]],
                    slot_bracket: Optional[Callable[[int], Any]] = None,
                    ) -> List[DecodedResult]:
        """Retire every slot of `pairs` ([(idx, example), ...]) and
        return their results in that order, with ONE device->host read
        for all of them.

        Three steps, each a child phase of the caller's: every slot's
        unpack program is dispatched (`decode/unpack/dispatch`, the one
        `unpack_slot_jit` compile) and its pages and activity bit
        released; then every output of every slot comes back in one
        `decode/unpack/fetch`; then each slot's `decode/unpack/result`.
        `slot_bracket(idx)`, where given, is the context one slot's
        dispatch and release run in (the scheduler's per-slot phase)."""
        import jax

        for idx, _ in pairs:
            if not self._active[idx]:
                raise AssertionError(f"slot {idx} is not resident")
        bracket = slot_bracket or (lambda idx: contextlib.nullcontext())
        outs = []
        for idx, _ in pairs:
            with bracket(idx):
                outs.append(self._jitted(
                    "decode/unpack_slot_jit", beam_search.unpack_slot_jit,
                    self._hps, self._state, idx, self._table[idx],
                    phase="decode/unpack/dispatch"))
                self._free_slot_pages(idx)
                self._active[idx] = False
        with self._prof.phase("decode/unpack/fetch"):
            # the harvest's one sanctioned sync: device_get starts every
            # copy before it waits on any, so the slots' reads overlap
            fetched = jax.device_get(outs)
        return [self._slot_result(out, example)
                for out, (_, example) in zip(fetched, pairs)]

    def _slot_result(self, out, example) -> DecodedResult:
        """One retired slot's host-side output -> its DecodedResult and
        the decoder's counters (`decode/unpack/result`)."""
        with self._prof.phase("decode/unpack/result"):
            res = self._dec._make_result(
                out.tokens, int(out.length), out.attn_dists, out.p_gens,
                avg_log_prob=float(out.avg_log_prob),
                uuid=example.uuid, article=example.original_article,
                reference=example.reference,
                abstract_sents=example.original_abstract_sents,
                art_oovs=example.article_oovs)
            self._dec._c_requests.inc()
            self._dec._c_beams.inc()
            self._dec._c_tokens.inc(len(res.decoded_words))
        return res

    def _free_slot_pages(self, idx: int) -> None:
        """Return slot `idx`'s pages to the arena and point its table
        row back at the scratch page.  Safe after the unpack dispatch:
        jit outputs are fresh buffers, so a later pack's scatter into
        the recycled pages cannot race the retiring gather."""
        ids = self._page_rows.pop(idx, None)
        if ids is not None:
            self._arena.free(ids)
            self._table[idx] = self._arena_pages

    def release(self, idx: int) -> None:
        """Free slot `idx` WITHOUT unpacking (deadline eviction): the
        stale state is masked out until the next pack overwrites it,
        and the slot's pages go straight back to the arena."""
        self._free_slot_pages(idx)
        self._active[idx] = False

    def active_count(self) -> int:
        return int(self._active.sum())

    def cache_sizes(self) -> Dict[str, int]:
        """Jit-cache entry counts of the four decode kernels plus the
        bucketed prefill — the 'bounded compile cache' evidence (tests
        assert the decode kernels never grow after warmup and prefill
        stays at one entry per serve bucket)."""
        out: Dict[str, int] = {}
        for fn in (beam_search.init_slots_jit, beam_search.prefill_jit,
                   beam_search.pack_slot_jit, beam_search.step_slots_jit,
                   beam_search.unpack_slot_jit):
            try:
                out[fn.__wrapped__.__name__] = fn._cache_size()
            except Exception:  # tslint: disable=TS005 — private jax API; absent on some builds
                pass
        return out
