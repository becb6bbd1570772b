"""Hierarchical-summarizer smoke (ISSUE 19): a multi-chunk document
through the REAL pipeline stage on a real tiny model — the no-hardware
proof that the map-reduce long-document path works end to end:

  * framed rows (pipeline/codec.py "doc#i/n") reassemble into one
    document and fan out chunk-by-chunk through a live ServingServer,
    with the reduce pass resolving the parent exactly once;
  * an APPEND frame-set for the same doc id re-summarizes the grown
    document, and every pre-append chunk is served from the front-door
    cache — deduplication by construction: the engine decodes only the
    appended chunks plus one reduce;
  * the reduce output's copy fidelity is observed per revision.

The committed scheduling claims (fan-out makespan vs sequential, the
append cache-hit floor) live in SERVE_SLO.json "hierarchical" and are
enforced by tests/test_serve_slo.py over virtual time; this smoke
proves the THREADED path on a real model.  Wired into scripts/repro.sh.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import shlex  # noqa: E402
import tempfile  # noqa: E402

from textsummarization_on_flink_tpu import obs  # noqa: E402
from textsummarization_on_flink_tpu.checkpoint.checkpointer import (  # noqa: E402
    Checkpointer,
)
from textsummarization_on_flink_tpu.config import HParams  # noqa: E402
from textsummarization_on_flink_tpu.data.vocab import Vocab  # noqa: E402
from textsummarization_on_flink_tpu.pipeline import codec  # noqa: E402
from textsummarization_on_flink_tpu.pipeline.estimator import (  # noqa: E402
    SummarizationModel,
    train_dir_for,
)
from textsummarization_on_flink_tpu.pipeline.io import (  # noqa: E402
    CollectionSink,
    CollectionSource,
    DataTypes,
)
from textsummarization_on_flink_tpu.train import trainer  # noqa: E402

#: 11 words cycled over 8-word chunks: every chunk starts at a distinct
#: phase of the cycle, so no two chunks are textually identical and an
#: intra-document cache hit can never inflate the append-path pins
WORDS = "the quick brown fox jumped over a lazy dog again .".split()
CHUNK_WORDS = 8
DOC_CHUNKS = 4
APPEND_CHUNKS = 2


def _words(start: int, count: int) -> str:
    return " ".join(WORDS[i % len(WORDS)] for i in range(start, start + count))


def main() -> None:
    tmp = tempfile.mkdtemp(prefix="hiersum_smoke_")
    vocab = Vocab(words=WORDS)
    hps = HParams(mode="decode", batch_size=2, hidden_dim=16, emb_dim=8,
                  vocab_size=vocab.size(), max_enc_steps=16,
                  max_dec_steps=6, beam_size=2, min_dec_steps=1,
                  max_oov_buckets=4, serve_max_wait_ms=50.0,
                  serve_max_queue=64, serve_coalesce=True,
                  serve_cache_entries=32, hier_chunk_words=CHUNK_WORDS,
                  log_root=tmp, exp_name="exp")
    # the pipeline stage restores the server's weights from the
    # train-dir hand-off (estimator.py train_dir_for) — seed it with an
    # init state, the same contract a finished training run leaves
    state = trainer.init_train_state(hps, vocab.size(), seed=0)
    Checkpointer(train_dir_for(hps), hps=hps).save(state)

    doc = _words(0, DOC_CHUNKS * CHUNK_WORDS)
    tail = _words(DOC_CHUNKS * CHUNK_WORDS, APPEND_CHUNKS * CHUNK_WORDS)
    frames = codec.frame_document_rows("doc", doc, "ref .", 16)
    frames += codec.frame_document_rows("doc", tail, "", 16)
    rows = [(u, a, "", r) for (u, a, r) in frames]

    model = SummarizationModel()
    (model.set_inference_selected_cols(["uuid", "article", "reference"])
          .set_inference_output_cols(["uuid", "article", "summary",
                                      "reference"])
          .set_inference_output_types([DataTypes.STRING] * 4))
    model.set_inference_hyper_params(shlex.split(hps.to_argv()))
    sink = CollectionSink()
    model.with_vocab(vocab).transform(CollectionSource(rows), sink,
                                      hierarchical=True)

    reg = obs.registry()
    assert [r[0] for r in sink.rows] == ["doc@r1", "doc@r2"], sink.rows
    assert all(r[2] for r in sink.rows), "empty summary out of the reduce"
    docs = int(reg.counter("serve/hier_documents_total").value)
    chunks = int(reg.counter("serve/hier_chunks_total").value)
    hits = int(reg.counter("serve/hier_chunk_cache_hits_total").value)
    reused = int(reg.counter("serve/hier_chunks_reused_total").value)
    decodes = int(reg.counter("serve/completed_total").value)
    partial = int(reg.counter("serve/hier_partial_failures_total").value)
    fid = reg.histogram("serve/hier_copy_fidelity")
    assert docs == 2 and partial == 0, (docs, partial)
    assert chunks == 2 * DOC_CHUNKS + APPEND_CHUNKS, chunks
    # THE append pin: every pre-append chunk cache-hits at submit, and
    # the engine only ever decoded chunks once — plus one reduce per
    # revision (the reduce inputs differ, so both decode)
    assert hits == DOC_CHUNKS, f"expected {DOC_CHUNKS} cache hits, {hits}"
    assert reused == DOC_CHUNKS, reused
    assert decodes == (DOC_CHUNKS + 1) + (APPEND_CHUNKS + 1), decodes
    assert fid.count == 2, fid.count
    print(f"hiersum smoke OK: 2 revisions, {chunks} chunk submits, "
          f"{hits} append cache hits, {decodes} decodes, "
          f"mean copy fidelity {fid.mean:.2f}")


if __name__ == "__main__":
    from textsummarization_on_flink_tpu.utils import (
        set_default_compile_cache,
    )

    set_default_compile_cache()
    main()
