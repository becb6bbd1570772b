"""chip_smoke.py — the system's main path on one TPU chip, end to end.

    python chip_smoke.py              # one chip: train, decode, serve,
                                      # transformer, kernels
    python chip_smoke.py --multichip  # four chips: sharded train + decode
                                      # against the one-device run, only

The quickest proof that the system still starts on the chip: it drives
train -> beam search -> serving through the entry points a user calls
(`cli.setup_training`, `BeamSearchDecoder`, `ServingServer`) at the
pointer-generator's reference width (`HParams()` defaults: hidden 256,
emb 128, vocab 50 000, enc 400, dec 100, batch 16, beam 4), checks what
comes out by the repo's own means, and prints one JSON line per phase.
Everything it reads is generated inside the run from a seed: a synthetic
vocabulary, chunked tf.Example files, random initial weights.

ONE process, the only one that touches jax (a chip belongs to one
process at a time).  It refuses to start unless jax's first device is a
TPU — there is no CPU mode; tests call the phase functions directly
with tiny HParams.  Any phase that raises or fails a check prints that
phase's error line and the script exits non-zero.  Only on full success
is the last stdout line exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Comparisons between differently compiled searches (loop kinds, serve
engines, sharded vs single device) are token-for-token; where two
hypotheses differ the line names the article, the first differing step
and both `avg_log_prob`s, and a relative gap over TIE_BOUND fails the
phase — under it the pair is a near-tie that the chip's
reduced-precision f32 matmuls may legitimately flip, and is counted,
not hidden.  Matmul precision is never raised for a program under test;
only REFERENCES run under `jax.default_matmul_precision("highest")`: the
kernels phase's XLA formula, and the one-device trainer that measures
what default precision itself costs (the `--multichip` parameters'
yardstick).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import tempfile
import threading
import time
import traceback

import numpy as np

SEED = 22
# relative avg_log_prob gap under which two differing hypotheses count
# as a near-tie (see module docstring).  The v5e showed gaps up to
# 1.0e-5 between the micro-batch engine (bucket-shaped matmuls) and the
# slot engines; the issue's starting bound of 1e-2 was tightened to this
TIE_BOUND = 1e-4
# sharded-vs-single-device loss agreement at the chip's default matmul
# precision (tests/test_parallel.py's 2e-5 is a CPU bound)
MULTICHIP_LOSS_RTOL = 1e-2
# sharded-vs-single-device PARAMETERS after the same steps, leaf by leaf.
# The yardstick is like for like: how far the one-device program lands
# from ITSELF run at highest matmul precision.  The two partitionings
# round the same math through differently shaped default-precision MXU
# passes, so the sharded run may be as far from the one-device run as
# that, and no further; at highest precision the v5e showed the two
# within 4e-7 of each leaf's largest magnitude (f32 sums in another
# order: the floor below), at default within 0.29 of the yardstick
MULTICHIP_PARAM_FLOOR_RTOL = 1e-6
# ...with an absolute floor for the leaf whose gradient is a sum that
# cancels: this bias is added to every encoder position's pre-tanh
# attention feature alike, so its gradient is (tanh' being near 1 at
# these weights) the softmax's gradient summed over positions, which is
# zero.  What is left of the sum — and, the bias starting at zero, the
# whole leaf: 1e-7 after 4 steps where its neighbours hold 1e-2 — is
# the rounding residue of much larger terms, with no scale of its own
# for a relative floor (its sibling `linear_kernel` moves as little,
# behind its init scale)
MULTICHIP_CANCELLING_LEAVES = ("['decoder']['attention']['linear_bias']",)
MULTICHIP_CANCELLING_ATOL = 1e-8
# the flash block against the highest-precision einsum formula, in units
# of what the SAME formula loses at the chip's default matmul precision
# (the block's projections and the kernel's dots run at that precision
# too): like for like, so a kernel regression of 2x fails.  The floor is
# tests/test_transformer.py's interpret-mode tolerance, the cap is
# bench.py's on-hardware flash gate; both are shares of the output scale
FLASH_VS_FORMULA = 1.5
FLASH_TOL_FLOOR = 2e-3
FLASH_TOL_CAP = 1e-2
SERVE_REQUESTS = 24
SERVE_THREADS = 4


class SmokeFailure(AssertionError):
    """A phase check that did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------------------------------
# compile accounting: jax's own monitoring events, so every compile in
# the process is counted whichever module launched it
# --------------------------------------------------------------------------

class CompileMeter:
    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               _BACKEND)

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self._lock = threading.Lock()

    def install(self) -> "CompileMeter":
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def _on_secs(self, event: str, secs: float, **kw) -> None:
        if event in self._EVENTS:
            with self._lock:
                self.seconds += secs
                if event == self._BACKEND:
                    self.compiles += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return self.seconds, self.compiles, self.cache_hits


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_phase(name: str, meter: CompileMeter, fn, *args) -> bool:
    """Run one phase, print its JSON line; False when it failed."""
    s0, c0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    line = {"phase": name}
    try:
        checked = fn(*args)
        line["passed"] = True
        line["checked"] = checked
    except Exception as e:  # the phase boundary: report, then fail the run
        line["passed"] = False
        line["error"] = f"{type(e).__name__}: {e}"
        line["traceback"] = traceback.format_exc()[-2000:]
    total = time.perf_counter() - t0
    s1, c1, h1 = meter.snapshot()
    line.update(seconds=round(total, 2),
                compile_seconds=round(s1 - s0, 2),
                # trace, lowering and backend-compile events can nest, so
                # their sum may pass the wall time of a compile-only phase
                run_seconds=round(max(total - (s1 - s0), 0.0), 2),
                compiles=c1 - c0, compile_cache_hits=h1 - h0,
                peak_bytes_in_use=peak_bytes())
    print(json.dumps(line), flush=True)
    return line["passed"]


# --------------------------------------------------------------------------
# seeded data: vocabulary, chunk files, request articles
# --------------------------------------------------------------------------

def make_vocab(hps):
    from textsummarization_on_flink_tpu.data.vocab import Vocab

    vocab = Vocab(words=[f"w{i}" for i in range(hps.vocab_size - 4)])
    check(vocab.size() == hps.vocab_size,
          f"vocab size {vocab.size()} != hps.vocab_size {hps.vocab_size}")
    return vocab


def _article(rng, n_words: int, pool: int) -> str:
    """n_words seeded words from a recurring subset of the vocabulary,
    about one in a hundred out of vocabulary (the copy path's input)."""
    ids = rng.randint(0, pool, size=n_words)
    oov = rng.rand(n_words) < 0.01
    return " ".join(f"oov{i}" if o else f"w{i}" for i, o in zip(ids, oov))


def write_examples(dirpath: str, prefix: str, hps, n: int, draw_len, rng):
    """n tf.Example (article, abstract) records as chunk files; returns
    the glob.  `draw_len()` gives the next article's word count."""
    from textsummarization_on_flink_tpu.data import TFExample
    from textsummarization_on_flink_tpu.data.chunks import write_chunked

    pool = min(hps.vocab_size - 4, 2000)
    exs = []
    for _ in range(n):
        n_abs = rng.randint(max(2, hps.max_dec_steps // 2),
                            hps.max_dec_steps)
        abstract = "<s> " + _article(rng, n_abs, pool) + " . </s>"
        exs.append(TFExample()
                   .set_bytes("article",
                              _article(rng, draw_len(), pool).encode())
                   .set_bytes("abstract", abstract.encode()))
    os.makedirs(dirpath, exist_ok=True)
    write_chunked(os.path.join(dirpath, prefix), exs, chunk_size=128)
    return os.path.join(dirpath, f"{prefix}_*.bin")


def make_data(work: str, hps, seed: int = SEED):
    """(train glob, mixed-length decode glob of one batch) under work."""
    rng = np.random.RandomState(seed)
    T = hps.max_enc_steps
    train = write_examples(
        os.path.join(work, "data"), "train", hps, 16 * hps.batch_size,
        lambda: rng.randint(T // 2, T + T // 4 + 1), rng)
    mixed = write_examples(
        os.path.join(work, "data"), "decode", hps, hps.batch_size,
        lambda: rng.randint(max(4, T // 16), T + 1), rng)
    return train, mixed


class Replay:
    """A batcher over batches already built: next_batch() -> Batch|None.
    Lets two runs consume the SAME batches in the same order (the
    threaded Batcher's order is not reproducible)."""

    def __init__(self, batches):
        self._it = iter(list(batches))

    def next_batch(self):
        return next(self._it, None)


def read_batches(pattern: str, vocab, hps, limit: int = 0, **kw):
    from textsummarization_on_flink_tpu.data.batcher import Batcher

    batcher = Batcher(pattern, vocab, hps, single_pass=True, **kw)
    out = []
    while not limit or len(out) < limit:
        batch = batcher.next_batch()
        if batch is None:
            break
        out.append(batch)
    return out


def read_losses(train_dir: str):
    """Per-step losses the Trainer's SummaryWriter recorded."""
    out = {}
    with open(os.path.join(train_dir, "events.jsonl"),
              encoding="utf-8") as f:
        for line in f:
            rec = json.loads(line)
            if "loss" in rec:
                out[int(rec["step"])] = float(rec["loss"])
    return [out[k] for k in sorted(out)]


def train_dir_of(hps) -> str:
    return os.path.join(hps.log_root, hps.exp_name, "train")


# --------------------------------------------------------------------------
# the tie rule
# --------------------------------------------------------------------------

def compare_hyps(base_name: str, base, other_name: str, other) -> dict:
    """Row-for-row comparison of two runs' hypotheses.  base/other:
    {key: (token-or-word list, avg_log_prob)}.  Returns the counted
    near-ties; a difference over TIE_BOUND raises."""
    check(set(base) == set(other),
          f"{base_name} and {other_name} answered different requests")
    near = []
    for key in sorted(base):
        toks_a, lp_a = base[key]
        toks_b, lp_b = other[key]
        if list(toks_a) == list(toks_b):
            continue
        step = next((i for i, (a, b) in enumerate(zip(toks_a, toks_b))
                     if a != b), min(len(toks_a), len(toks_b)))
        gap = abs(lp_a - lp_b) / max(abs(lp_a), abs(lp_b), 1e-30)
        entry = {"article": str(key), "first_differing_step": step,
                 f"avg_log_prob_{base_name}": lp_a,
                 f"avg_log_prob_{other_name}": lp_b,
                 "relative_gap": gap}
        check(gap <= TIE_BOUND,
              f"{other_name} differs from {base_name} beyond a near-tie: "
              f"{json.dumps(entry)}")
        near.append(entry)
    return {"rows": len(base), "token_equal": len(base) - len(near),
            "near_ties": near}


# --------------------------------------------------------------------------
# phases (each returns what it checked; tests call these with tiny hps)
# --------------------------------------------------------------------------

def phase_train(hps, vocab, train_glob: str) -> dict:
    """`cli.setup_training` (Batcher over the chunk files -> Trainer with
    prefetcher, windowed metrics fetch, checkpoint save) for 8 steps at
    the default steps_per_dispatch and once at 4, then a restore."""
    import jax

    from textsummarization_on_flink_tpu import cli
    from textsummarization_on_flink_tpu.checkpoint import (
        checkpointer as ckpt_lib,
    )
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib

    steps = 8
    checked = {}
    for tag, spd in (("spd_default", hps.steps_per_dispatch), ("spd4", 4)):
        run = hps.replace(mode="train", data_path=train_glob,
                          exp_name=f"train_{tag}", num_steps=steps,
                          steps_per_dispatch=spd)
        run.validate()
        state = cli.setup_training(run, vocab)
        losses = read_losses(train_dir_of(run))
        check(len(losses) == steps and all(map(math.isfinite, losses)),
              f"{tag}: expected {steps} finite losses, got {losses}")
        check(int(state.step) == steps,
              f"{tag}: state.step {int(state.step)} != {steps}")
        checked[tag] = {"steps_per_dispatch": spd, "losses": losses}
        if tag != "spd_default":
            continue
        init = trainer_lib.init_train_state(run, vocab.size())
        still = [jax.tree_util.keystr(path) for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(init.params)[0],
            jax.tree_util.tree_leaves(state.params))
            if np.array_equal(np.asarray(a), np.asarray(b))]
        # a leaf the loss does not reach (the coverage weight with
        # coverage off) legitimately stays; most must move
        check(len(still) <= 1, f"parameter leaves unchanged after "
                               f"{steps} steps: {still}")
        saved = ckpt_lib.state_to_arrays(state)
        restored = ckpt_lib.state_to_arrays(
            ckpt_lib.Checkpointer(train_dir_of(run), hps=run).restore())
        check(saved.keys() == restored.keys()
              and all(np.array_equal(saved[k], restored[k])
                      for k in saved),
              "restored checkpoint differs from the saved state")
        checked["unchanged_param_leaves"] = still
        checked["restore_equals_saved"] = True
        checked["checkpoint_leaves"] = len(saved)
    return checked


def _trained_params(hps):
    """The train phase's parameters, off its checkpoint."""
    from textsummarization_on_flink_tpu.checkpoint import (
        checkpointer as ckpt_lib,
    )

    _, flat = ckpt_lib.load_ckpt(
        train_dir_of(hps.replace(exp_name="train_spd_default")),
        max_retries=0)
    return ckpt_lib.arrays_to_state(flat).params


LOOP_KINDS = ("chunked", "scan", "while")  # the first is the base


def phase_decode(hps, vocab, decode_glob: str) -> dict:
    """`BeamSearchDecoder` single pass over one batch of mixed-length
    articles with the train phase's checkpoint, TS_BEAM_LOOP unset; then
    the same batch through each loop kind, token for token."""
    import jax

    from textsummarization_on_flink_tpu.decode import beam_search
    from textsummarization_on_flink_tpu.decode.decoder import (
        BeamSearchDecoder,
    )

    check("TS_BEAM_LOOP" not in os.environ,
          "unset TS_BEAM_LOOP: this phase reports what `auto` resolves")
    auto = beam_search._loop_kind()
    dhps = hps.replace(mode="decode", single_pass=True,
                       exp_name="train_spd_default")
    batches = read_batches(decode_glob, vocab, dhps,
                           decode_batch_mode="distinct")
    n_articles = sum(int(np.sum(b.real_mask)) for b in batches)
    decoder = BeamSearchDecoder(
        dhps, vocab, Replay(batches), train_dir=train_dir_of(dhps),
        max_ckpt_retries=0,
        decode_root=os.path.join(hps.log_root, "decode_single_pass"))
    results = []
    decoder.decode(with_rouge=False, result_sink=results.append)
    check(len(results) == n_articles == hps.batch_size,
          f"{len(results)} summaries for {n_articles} articles "
          f"(batch {hps.batch_size})")
    lengths = [len(r.decoded_words) for r in results]
    check(min(lengths) >= 1, f"empty summary among lengths {lengths}")

    params = _trained_params(hps)
    arrays = batches[0].as_arrays()
    real = np.flatnonzero(batches[0].real_mask)
    runs = {}
    for kind in LOOP_KINDS:
        out = beam_search.run_beam_search_jit(
            params, dhps, arrays, loop=kind,
            chunk=beam_search.resolved_chunk(kind))
        out = jax.device_get(out)
        check(bool(np.all(np.isfinite(out.avg_log_prob))),
              f"{kind}: non-finite avg_log_prob")
        runs[kind] = {int(b): ([int(t) for t in
                                out.tokens[b][:int(out.length[b])]],
                               float(out.avg_log_prob[b])) for b in real}
    # the decoder ran the kind `auto` resolved on these same arrays
    for b, res in zip(real, results):
        check(abs(res.avg_log_prob - runs[auto][int(b)][1]) <= 1e-6,
              f"decoder result {b} is not the {auto} search's")
    base = LOOP_KINDS[0]
    return {"summaries": len(results), "min_length": min(lengths),
            "max_length": max(lengths), "loop_auto": auto,
            "loop_equality": {
                kind: compare_hyps(base, runs[base], kind, runs[kind])
                for kind in LOOP_KINDS[1:]}}


def _serve_rows(hps, buckets, rng):
    """SERVE_REQUESTS (uuid, article, summary, reference) rows, article
    lengths cycling through the serve buckets."""
    pool = min(hps.vocab_size - 4, 2000)
    rows = []
    for i in range(SERVE_REQUESTS):
        k = i % len(buckets)
        lo, hi = (buckets[k - 1] if k else 0), buckets[k]
        n = rng.randint(max(lo + 1, hi // 4), hi + 1)  # routes to bucket k
        rows.append((f"req-{i:02d}", _article(rng, n, pool), "",
                     f"reference {i} ."))
    return rows


def _serve_engine(name: str, ehps, vocab, rows, buckets, meter):
    """One engine: warm every bucket, then SERVE_REQUESTS requests from
    SERVE_THREADS submitter threads (CollectionSource rows in,
    CollectionSink rows out — ServingServer.serve's per-row contract,
    keeping the futures for each result's avg_log_prob).  Returns
    (what was checked, {uuid: (words, avg_log_prob)})."""
    from textsummarization_on_flink_tpu import obs
    from textsummarization_on_flink_tpu.config import (
        resolve_refill_chunk,
        resolve_serve_slots,
    )
    from textsummarization_on_flink_tpu.decode.decoder import (
        BeamSearchDecoder,
    )
    from textsummarization_on_flink_tpu.obs import profile as profile_lib
    from textsummarization_on_flink_tpu.pipeline.io import (
        CollectionSink,
        CollectionSource,
    )
    from textsummarization_on_flink_tpu.serve.server import (
        SERVE_COLS,
        ServingServer,
    )

    ehps.validate()
    decoder = BeamSearchDecoder(
        ehps.replace(single_pass=False), vocab, batcher=None,
        train_dir=train_dir_of(ehps.replace(exp_name="train_spd_default")),
        max_ckpt_retries=0,
        decode_root=os.path.join(ehps.log_root, f"serve_{name}"))
    engine = None
    if ehps.serve_mode == "continuous":
        engine = decoder.slot_engine(slots=resolve_serve_slots(ehps),
                                     chunk=resolve_refill_chunk(ehps))
    server = ServingServer(ehps, vocab, decoder=decoder, engine=engine)
    prof = profile_lib.profiler_for(obs.registry_for(ehps))
    ledger0 = prof.warm_set_size()
    blocked = obs.registry_for(ehps).counter(
        "serve/arena_alloc_failures_total")
    blocked0 = blocked.value
    sink = CollectionSink()
    futures = {}
    errors = []

    def submitter(part) -> None:
        try:
            source = CollectionSource(part)
            for row in source.rows():
                uuid, article, reference = source.schema.project_row(
                    row, list(SERVE_COLS))
                fut = server.submit(article, uuid=uuid,
                                    reference=reference, block=True)
                fut.add_done_callback(
                    lambda f: f.error is None
                    and sink.write(f.result().as_row()))
                futures[uuid] = fut
        except Exception as e:  # re-raised by the phase after the join
            errors.append(e)

    with server:
        # exactly b words -> enc_len b -> bucket b itself compiles now;
        # one at a time, or the micro-batcher coalesces the warmers
        # into one dispatch at the largest bucket
        for b in buckets:
            server.submit(" ".join(f"w{i % 50}" for i in range(b)),
                          uuid=f"warm-{b}", block=True).result(timeout=900)
        ledger1, xla1 = prof.warm_set_size(), meter.snapshot()[1]
        threads = [threading.Thread(target=submitter,
                                    args=(rows[i::SERVE_THREADS],))
                   for i in range(SERVE_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        check(not any(t.is_alive() for t in threads),
              f"{name}: a submitter thread did not finish")
        if errors:
            raise errors[0]
        results = {u: f.result(timeout=900) for u, f in futures.items()}
    ledger_after = prof.warm_set_size() - ledger1
    xla_after = meter.snapshot()[1] - xla1
    check(len(results) == len(rows) == len(sink.rows),
          f"{name}: {len(results)} results / {len(sink.rows)} sink rows "
          f"for {len(rows)} requests")
    check(ledger_after == 0 and xla_after == 0,
          f"{name}: compiles after warm-up (ledger {ledger_after}, "
          f"xla {xla_after}): {prof.compile_stats()}")
    info = {"requests": len(results), "warm_set": ledger1 - ledger0,
            "compiles_after_warmup": ledger_after,
            "xla_compiles_after_warmup": xla_after}
    if engine is not None:
        arena = engine.arena_stats()
        check(arena["free"] == arena["capacity"] and arena["in_use"] == 0,
              f"{name}: arena pages not all free at the end: {arena}")
        info["arena_pages"] = arena["capacity"]
        info["arena_pages_free_at_end"] = arena["free"]
        info["arena_alloc_failures"] = int(blocked.value - blocked0)
    hyps = {u: (r.decoded_words, r.avg_log_prob)
            for u, r in results.items()}
    check(all(len(w) >= 1 for w, _ in hyps.values()),
          f"{name}: an empty summary")
    return info, hyps


def phase_serve(hps, vocab, meter) -> dict:
    """`ServingServer` over the train phase's checkpoint: micro-batch,
    continuous with no arena option (every slot at full length),
    continuous over an arena of two full-length articles (admissions
    wait for pages and pages are recycled) — the three must return the
    same tokens row for row, and both arenas must drain."""
    from textsummarization_on_flink_tpu.config import (
        parse_bucket_spec,
        resolve_enc_block,
    )

    shps = hps.replace(mode="decode", serve_max_queue=4 * SERVE_REQUESTS)
    buckets = parse_bucket_spec(shps.serve_buckets, shps.max_enc_steps)
    rows = _serve_rows(shps, buckets, np.random.RandomState(SEED + 1))
    pages = 2 * -(-shps.max_enc_steps // resolve_enc_block(shps))
    engines = (
        ("microbatch", shps.replace(serve_mode="microbatch")),
        ("continuous", shps.replace(serve_mode="continuous")),
        ("continuous_paged", shps.replace(serve_mode="continuous",
                                          serve_arena_pages=pages)),
    )
    checked = {"buckets": buckets}
    hyps = {}
    for name, ehps in engines:
        checked[name], hyps[name] = _serve_engine(
            name, ehps, vocab, rows, buckets, meter)
    checked["engine_equality"] = {
        name: compare_hyps("microbatch", hyps["microbatch"], name,
                           hyps[name])
        for name in ("continuous", "continuous_paged")}
    arenas = compare_hyps("continuous", hyps["continuous"],
                          "continuous_paged", hyps["continuous_paged"])
    check(arenas["token_equal"] == arenas["rows"],
          f"the full and the tight arena decoded different tokens: "
          f"{json.dumps(arenas['near_ties'])}")
    checked["arena_equality"] = arenas
    return checked


def phase_transformer(hps, vocab, train_glob: str, decode_glob: str) -> dict:
    """The transformer family at the same width: 4 train steps through
    `cli.setup_training`, then one batch through `BeamSearchDecoder`."""
    from textsummarization_on_flink_tpu import cli
    from textsummarization_on_flink_tpu.decode.decoder import (
        BeamSearchDecoder,
    )

    steps = 4
    run = hps.replace(model_family="transformer", mode="train",
                      data_path=train_glob, exp_name="transformer",
                      num_steps=steps)
    run.validate()
    state = cli.setup_training(run, vocab)
    losses = read_losses(train_dir_of(run))
    check(len(losses) == steps and all(map(math.isfinite, losses)),
          f"expected {steps} finite losses, got {losses}")
    check(int(state.step) == steps, f"state.step {int(state.step)}")
    dhps = run.replace(mode="decode", single_pass=True)
    batches = read_batches(decode_glob, vocab, dhps,
                           decode_batch_mode="distinct")
    decoder = BeamSearchDecoder(
        dhps, vocab, Replay(batches), train_dir=train_dir_of(run),
        max_ckpt_retries=0,
        decode_root=os.path.join(hps.log_root, "decode_transformer"))
    results = []
    decoder.decode(with_rouge=False, result_sink=results.append)
    lengths = [len(r.decoded_words) for r in results]
    check(len(results) == hps.batch_size and min(lengths) >= 1,
          f"{len(results)} summaries, lengths {lengths}")
    check(all(math.isfinite(r.avg_log_prob) for r in results),
          "non-finite avg_log_prob")
    return {"losses": losses, "summaries": len(results),
            "min_length": min(lengths), "max_length": max(lengths)}


@contextlib.contextmanager
def env_set(name: str, value: str):
    """Set one of the package's trace-time TS_* switches for a block."""
    old = os.environ.get(name)
    os.environ[name] = value
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = old


def _max_err(got, ref, where=None):
    diff = np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64))
    if where is not None:
        diff = np.where(where, diff, 0.0)
    return float(diff.max())


def kernel_fused_attention(B: int, T: int, D: int, rtol: float,
                           seed: int = SEED) -> dict:
    """`fused_attention` under TS_PALLAS=on (simple or blocked kernel by
    size), compiled, against `_attention_xla` at highest precision."""
    import jax

    from textsummarization_on_flink_tpu.ops import pallas_attention as pa

    rng = np.random.RandomState(seed)
    lens = rng.randint(T // 2, T + 1, size=(B,))
    args = (rng.randn(B, T, D), rng.randn(B, T, D),
            np.arange(T)[None, :] < lens[:, None], rng.randn(B, D),
            np.abs(rng.randn(B, T)), rng.randn(D), rng.randn(D))
    args = tuple(jax.device_put(np.asarray(a, np.float32)) for a in args)
    with env_set("TS_PALLAS", "on"):
        got = jax.jit(lambda *a: pa.fused_attention(*a, True))(*args)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda *a: pa._attention_xla(*a, True))(*args)
    got, ref = jax.device_get((got, ref))
    errs = {"kernel": ("blocked" if T * D > pa._SIMPLE_KERNEL_MAX_ELEMS
                       else "simple"),
            "max_ctx_err": _max_err(got[0], ref[0]),
            "max_attn_err": _max_err(got[1], ref[1]),
            # tests/test_pallas_attention.py's atol: 1e-5 ctx, 1e-6 attn
            "ctx_tol": 1e-5 + rtol * float(np.abs(ref[0]).max()),
            "attn_tol": 1e-6 + rtol * float(np.abs(ref[1]).max())}
    errs["passed"] = (errs["max_ctx_err"] <= errs["ctx_tol"]
                      and errs["max_attn_err"] <= errs["attn_tol"])
    return errs


def flash_tolerance(formula_err: float, scale: float) -> float:
    """The flash block's allowed error against the highest-precision
    formula: FLASH_VS_FORMULA times what the default-precision formula
    loses, within [FLASH_TOL_FLOOR, FLASH_TOL_CAP] of the output scale."""
    scale = max(scale, 1.0)
    return min(max(FLASH_VS_FORMULA * formula_err, FLASH_TOL_FLOOR * scale),
               FLASH_TOL_CAP * scale)


def kernel_flash(B: int, T: int, hidden: int, heads: int,
                 seed: int = SEED) -> dict:
    """The transformer's self-attention block under TS_FLASH=on,
    compiled, against the einsum formula at highest precision — real
    rows only (the kernel leaves padding-query rows undefined)."""
    import jax
    import jax.numpy as jnp

    from textsummarization_on_flink_tpu.config import HParams
    from textsummarization_on_flink_tpu.models import transformer as tfm

    hps = HParams(model_family="transformer", hidden_dim=hidden,
                  num_heads=heads, max_enc_steps=T, batch_size=B)
    rng = np.random.RandomState(seed)
    p = {k: jnp.asarray(rng.randn(hidden, hidden) * hidden ** -0.5,
                        jnp.float32) for k in ("wq", "wk", "wv", "wo")}
    x = jnp.asarray(rng.randn(B, T, hidden), jnp.float32)
    lens = rng.randint(T // 2, T + 1, size=(B,))
    mask = jnp.asarray(np.arange(T)[None] < lens[:, None], jnp.float32)

    def run(flash: str):
        # a fresh function per variant: jit caches traces by function
        # identity, and TS_FLASH is read while tracing
        def block(x):
            return tfm._self_attention(hps, p, x, mask, causal=False)

        with env_set("TS_FLASH", flash):
            return jax.jit(block)(x)

    got, plain = run("on"), run("off")
    with jax.default_matmul_precision("highest"):
        ref = run("off")
    got, plain, ref = jax.device_get((got, plain, ref))
    real = np.asarray(mask)[:, :, None] > 0
    scale = float(np.abs(np.where(real, ref, 0.0)).max())
    formula_err = _max_err(plain, ref, real)
    errs = {"T": T, "head_dim": hidden // heads,
            "max_err": _max_err(got, ref, real),
            # what the einsum formula itself loses at the chip's default
            # matmul precision: the yardstick for the kernel's error
            "formula_default_precision_err": formula_err,
            "flash_vs_default_formula": _max_err(got, plain, real),
            "tol": flash_tolerance(formula_err, scale)}
    errs["passed"] = errs["max_err"] <= errs["tol"]
    return errs


def phase_kernels() -> dict:
    """The Pallas kernels, compiled for the chip and run on it."""
    checked = {
        # tolerances start from tests/test_pallas_attention.py (simple:
        # 1e-5, blocked: 1e-4; the chip showed 5.7e-6 at worst).  The
        # flash block is held to flash_tolerance(): it showed 4.0e-3
        # and 1.7e-3 against the highest-precision formula where the
        # default-precision formula itself showed 4.4e-3 and 1.8e-3
        "fused_attention_simple": kernel_fused_attention(
            16, 400, 512, rtol=1e-5),
        "fused_attention_blocked": kernel_fused_attention(
            4, 4096, 512, rtol=1e-4),
        "flash_T400_hd32": kernel_flash(16, 400, 256, 8),
        "flash_T2048_hd128": kernel_flash(4, 2048, 1024, 8),
    }
    failed = [k for k, v in checked.items() if not v["passed"]]
    check(not failed, f"kernel/formula mismatch in {failed}: "
                      f"{json.dumps(checked)}")
    return checked


def _leaf_placements(tree) -> dict:
    """{leaf path: {spec, devices}} — where each leaf's shards live."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        sh = leaf.sharding
        out[jax.tree_util.keystr(path)] = {
            "spec": str(getattr(sh, "spec", sh)),
            "devices": sorted(d.id for d in sh.device_set)}
    return out


def _param_drift(single, sharded, single_highest) -> list:
    """Per leaf: the largest |sharded - single|, the yardstick
    |single - single_highest|, the leaf's largest magnitude, and what
    the difference is allowed to be (the yardstick, or the f32 floor
    where that is larger); worst share of the allowance first."""
    import jax

    out = []
    for (path, a), b, ref in zip(
            jax.tree_util.tree_flatten_with_path(single)[0],
            jax.tree_util.tree_leaves(sharded),
            jax.tree_util.tree_leaves(single_highest)):
        a, b, ref = (np.asarray(x, np.float64) for x in (a, b, ref))
        name = jax.tree_util.keystr(path)
        scale = float(np.abs(a).max())
        floor = (MULTICHIP_CANCELLING_ATOL
                 if name in MULTICHIP_CANCELLING_LEAVES
                 else MULTICHIP_PARAM_FLOOR_RTOL * scale)
        yardstick = float(np.abs(a - ref).max())
        allowed = max(yardstick, floor)
        diff = float(np.abs(a - b).max())
        out.append({"leaf": name, "diff": diff, "scale": scale,
                    "default_vs_highest": yardstick, "allowed": allowed,
                    "of_allowed": diff / allowed if allowed else float(
                        diff > 0)})
    return sorted(out, key=lambda d: -d["of_allowed"])


def phase_multichip(hps, vocab, train_glob: str, decode_glob: str) -> dict:
    """Across chips: `Trainer` with dp=2, tp=2 against dp=tp=sp=1 on the
    same seed and batches (and, as the parameters' yardstick only, the
    one-device run at highest matmul precision), then
    `BeamSearchDecoder` with dp=4 against dp=1 on the same articles."""
    import jax

    from textsummarization_on_flink_tpu.decode.decoder import (
        BeamSearchDecoder,
    )
    from textsummarization_on_flink_tpu.parallel import mesh as mesh_lib
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib

    steps = 4
    thps = hps.replace(mode="train")
    batches = read_batches(train_glob, vocab, thps, limit=steps)
    check(len(batches) == steps, f"only {len(batches)} train batches")
    losses, states = {}, {}
    for tag, axes, precision in (("single", {}, None),
                                 ("dp2_tp2", {"dp": 2, "tp": 2}, None),
                                 ("single_highest", {}, "highest")):
        run = thps.replace(exp_name=f"multichip_{tag}", **axes)
        run.validate()
        trainer = trainer_lib.Trainer(run, vocab.size(), Replay(batches),
                                      train_dir=train_dir_of(run))
        # highest precision for the yardstick run only: the compared
        # programs never run under it
        with (jax.default_matmul_precision(precision) if precision
              else contextlib.nullcontext()):
            states[tag] = trainer.train(num_steps=steps)
        losses[tag] = read_losses(train_dir_of(run))
        check(len(losses[tag]) == steps
              and all(map(math.isfinite, losses[tag])),
              f"{tag}: losses {losses[tag]}")
    rel = [abs(a - b) / max(abs(a), 1e-30)
           for a, b in zip(losses["single"], losses["dp2_tp2"])]
    check(max(rel) <= MULTICHIP_LOSS_RTOL,
          f"sharded losses {losses['dp2_tp2']} differ from the "
          f"single-device {losses['single']} by {max(rel)} relative")
    # the losses can agree to the last f32 digit; the parameters show
    # that two differently partitioned programs really ran
    drift = _param_drift(states["single"].params,
                         states["dp2_tp2"].params,
                         states["single_highest"].params)
    check(drift[0]["of_allowed"] <= 1.0,
          f"sharded parameters drifted further from the single-device "
          f"run in {steps} steps than that run is from itself at highest "
          f"matmul precision; all leaves, worst first: {json.dumps(drift)}")
    placements = _leaf_placements(states["dp2_tp2"].params)
    train_devices = sorted({d for p in placements.values()
                            for d in p["devices"]})
    check(len(train_devices) == 4,
          f"dp=2 x tp=2 parameters live on devices {train_devices}")

    params = jax.device_get(states["single"].params)
    dhps = hps.replace(mode="decode", single_pass=True)
    dbatches = read_batches(decode_glob, vocab, dhps,
                            decode_batch_mode="distinct")
    hyps = {}
    for tag, axes in (("single", {}), ("dp4", {"dp": 4})):
        run = dhps.replace(**axes)
        run.validate()
        decoder = BeamSearchDecoder(
            run, vocab, batcher=None, params=params,
            decode_root=os.path.join(hps.log_root, f"multichip_{tag}"))
        check(decoder.sharded == bool(axes),
              f"{tag}: decoder.sharded is {decoder.sharded}")
        results = decoder.decode_batch(dbatches[0])
        hyps[tag] = {i: (r.decoded_words, r.avg_log_prob)
                     for i, r in enumerate(results)}
    decode_devices = sorted(
        d.id for d in mesh_lib.make_mesh(dhps.replace(dp=4))
        .mesh.devices.flat)
    check(len(decode_devices) == 4,
          f"dp=4 decode mesh spans devices {decode_devices}")
    return {"device_count": len(jax.devices()),
            "losses": losses, "max_relative_loss_diff": max(rel),
            "param_drift_worst": drift[:3],  # of 24 leaves, worst first
            "train_shard_devices": train_devices,
            "param_shardings": placements,
            "decode_mesh_devices": decode_devices,
            "decode_equality": compare_hyps("single", hyps["single"],
                                            "dp4", hyps["dp4"])}


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------

def success_line(devices) -> str:
    return json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip phase (sharded train and "
                         "decode against the one-device run)")
    args = ap.parse_args(argv)

    from textsummarization_on_flink_tpu.utils import (
        set_default_compile_cache,
    )

    cache_dir = set_default_compile_cache()
    import jax

    devices = jax.devices()
    need = 4 if args.multichip else 1
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"chip_smoke: needs {need} TPU device(s), jax found "
              f"{len(devices)} x {devices[0].platform!r}; there is no "
              f"CPU mode", file=sys.stderr)
        return 2
    meter = CompileMeter().install()
    t0 = time.perf_counter()
    print(json.dumps({"phase": "setup", "compile_cache_dir": cache_dir,
                      "jax": jax.__version__,
                      "device_kind": devices[0].device_kind,
                      "device_count": len(devices)}), flush=True)

    from textsummarization_on_flink_tpu.config import HParams

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        hps = HParams(log_root=work, seed=SEED)
        vocab = make_vocab(hps)
        train_glob, decode_glob = make_data(work, hps)
        if args.multichip:
            passed = [run_phase("multichip", meter, phase_multichip, hps,
                                vocab, train_glob, decode_glob)]
        else:
            serve_hps = hps.replace(batch_size=8, serve_slots=8,
                                    serve_buckets="100,200,400")
            passed = [
                run_phase("train", meter, phase_train, hps, vocab,
                          train_glob),
                run_phase("decode", meter, phase_decode, hps, vocab,
                          decode_glob),
                run_phase("serve", meter, phase_serve, serve_hps, vocab,
                          meter),
                run_phase("transformer", meter, phase_transformer, hps,
                          vocab, train_glob, decode_glob),
                run_phase("kernels", meter, phase_kernels),
            ]
    secs, compiles, hits = meter.snapshot()
    print(json.dumps({"phase": "summary", "passed": all(passed),
                      "seconds": round(time.perf_counter() - t0, 2),
                      "compile_seconds": round(secs, 2),
                      "compiles": compiles, "compile_cache_hits": hits,
                      "compile_cache_dir": cache_dir,
                      "peak_bytes_in_use": peak_bytes()}), flush=True)
    if not all(passed):
        return 1
    print(success_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
