"""Serve smoke: CollectionSource -> ServingServer -> CollectionSink on
the 8 synthetic rows (TensorFlowTest.createArticleData shape), tiny
model, CPU — the no-hardware proof that the concurrent serving path
(queue admission, micro-batching, bucket padding, future resolution,
sink fan-in) works end to end.  The continuous pass runs the
DISAGGREGATED path (ISSUE 11): mixed-length articles route through the
bucketed prefill stage into length-masked slots, with row-for-row
parity asserted against the single-stage micro-batch pass and the
prefill telemetry checked (every request prefilled, short articles at
sub-max buckets).  Wired into scripts/repro.sh.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import tempfile  # noqa: E402

from textsummarization_on_flink_tpu import obs  # noqa: E402
from textsummarization_on_flink_tpu.config import HParams  # noqa: E402
from textsummarization_on_flink_tpu.data.vocab import Vocab  # noqa: E402
from textsummarization_on_flink_tpu.pipeline.io import (  # noqa: E402
    CollectionSink,
    CollectionSource,
)
from textsummarization_on_flink_tpu.serve.server import (  # noqa: E402
    ServingServer,
)
from textsummarization_on_flink_tpu.train import trainer  # noqa: E402


def main() -> None:
    # mixed LENGTHS on purpose (ISSUE 11): even rows are short (3-word)
    # articles that bucket at 8, odd rows pad out toward the top bucket
    # — the continuous pass must route them to different prefill shapes
    # while staying row-identical with the micro-batch pass
    rows = [(f"uuid-{i}",
             f"article {i} ." if i % 2 == 0
             else f"article {i} " + ". article " * 5 + ".",
             "", f"reference {i} .")
            for i in range(8)]
    vocab = Vocab(words=["article", "reference", ".", "0", "1", "2", "3",
                         "4", "5", "6", "7"])
    hps = HParams(mode="decode", batch_size=2, hidden_dim=16, emb_dim=8,
                  vocab_size=vocab.size(), max_enc_steps=16, max_dec_steps=6,
                  beam_size=2, min_dec_steps=1, max_oov_buckets=4,
                  serve_max_wait_ms=50.0, serve_max_queue=32,
                  serve_buckets="8,16")
    params = trainer.init_train_state(hps, vocab.size(), seed=0).params

    # micro-batch mode (the ISSUE-4 baseline)
    server = ServingServer(hps, vocab, params=params,
                           decode_root=tempfile.mkdtemp(prefix="serve_smoke_"))
    sink = CollectionSink()
    with server:
        server.serve(CollectionSource(rows), sink)
    assert len(sink.rows) == 8, sink.rows
    assert {r[0] for r in sink.rows} == {f"uuid-{i}" for i in range(8)}
    fill = obs.registry().histogram("serve/batch_fill")
    p50 = obs.registry().histogram("serve/e2e_latency_seconds").percentile(0.5)
    print(f"serve smoke OK: 8 rows over {fill.count} micro-batch(es), "
          f"mean fill {fill.mean:.1f}, e2e p50 {p50 * 1000:.1f} ms")

    # continuous mode (ISSUE 6): same rows through the slotted engine;
    # summaries must match the micro-batch pass row for row
    hps_c = hps.replace(serve_mode="continuous", serve_slots=2,
                        serve_refill_chunk=2)
    server_c = ServingServer(
        hps_c, vocab, params=params,
        decode_root=tempfile.mkdtemp(prefix="serve_smoke_cont_"))
    sink_c = CollectionSink()
    with server_c:
        server_c.serve(CollectionSource(rows), sink_c)
    assert len(sink_c.rows) == 8, sink_c.rows
    by_uuid = {r[0]: r for r in sink.rows}
    by_uuid_c = {r[0]: r for r in sink_c.rows}
    assert by_uuid == by_uuid_c, "continuous/micro-batch row drift"
    reg = obs.registry()
    occ = reg.histogram("serve/slot_occupancy")
    # prefill/decode disaggregation evidence (ISSUE 11): every request
    # went through the bucketed prefill stage, and the short rows
    # really ran their encoder pass at the SUB-MAX bucket (a bucket
    # histogram pinned at max_enc_steps would mean the stage pads
    # everything to full width again)
    prefills = int(reg.counter("serve/prefill_total").value)
    bucket_h = reg.histogram("serve/prefill_bucket_len")
    assert prefills == 8, f"expected 8 prefills, saw {prefills}"
    assert bucket_h.count == 8
    assert bucket_h.mean < hps.max_enc_steps, (
        f"mean prefill bucket {bucket_h.mean:.1f} pinned at "
        f"max_enc_steps={hps.max_enc_steps}: short articles are not "
        f"routing to short encoder shapes")
    print(f"continuous smoke OK: 8 rows over {occ.count} chunk step(s), "
          f"mean occupancy {occ.mean:.2f}, "
          f"refills {int(reg.counter('serve/slot_refills_total').value)}, "
          f"prefills {prefills} (mean bucket {bucket_h.mean:.1f} of "
          f"{hps.max_enc_steps}), rows identical to micro-batch "
          f"(disaggregated prefill/decode path)")


if __name__ == "__main__":
    from textsummarization_on_flink_tpu.utils import (
        set_default_compile_cache,
    )

    set_default_compile_cache()
    main()
