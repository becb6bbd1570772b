"""One-mesh smoke (ISSUE 8): dp x tp train AND serve on the faked
8-device CPU mesh, end to end through the sharding registry.

  * train: the unified sharded step at dp=4 x tp=2 with every lever the
    registry composes — bf16 gradient wire annotation, --loss_chunk
    streaming vocab loss, bf16 Adagrad state — 3 real optimizer steps,
    finite losses, layouts preserved through the update.
  * serve: the SAME rows through BOTH serving engines at dp=2 x tp=2 —
    the micro-batch sharded beam search and the continuous slotted
    engine (resident state over dp, registry slot specs) — row-for-row
    identical to a single-device pass.

Wired into scripts/repro.sh (which exports the 8-device XLA flag); the
committed collective-byte claims live in BYTE_BUDGET.json's `comms`
section, enforced by tests/test_bytes_gate.py — this proves the paths
RUN, the gate proves what they move.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import tempfile  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from textsummarization_on_flink_tpu.config import HParams  # noqa: E402
from textsummarization_on_flink_tpu.data.batching import (  # noqa: E402
    Batch,
    SummaryExample,
)
from textsummarization_on_flink_tpu.data.vocab import Vocab  # noqa: E402
from textsummarization_on_flink_tpu.parallel import mesh as mesh_lib  # noqa: E402
from textsummarization_on_flink_tpu.pipeline.io import (  # noqa: E402
    CollectionSink,
    CollectionSource,
)
from textsummarization_on_flink_tpu.serve.server import (  # noqa: E402
    ServingServer,
)
from textsummarization_on_flink_tpu.train import trainer  # noqa: E402


def train_smoke() -> None:
    hps = HParams(hidden_dim=8, emb_dim=6, batch_size=8, max_enc_steps=16,
                  max_dec_steps=6, beam_size=2, min_dec_steps=1,
                  vocab_size=64, max_oov_buckets=8,
                  dp=4, tp=2, grad_allreduce_dtype="bfloat16",
                  loss_chunk=3, opt_state_dtype="bfloat16")
    hps.validate()
    vocab = Vocab(words=[f"w{i}" for i in range(60)], max_size=64)
    rng = np.random.RandomState(0)
    exs = [SummaryExample.build(
        " ".join(rng.choice([f"w{j}" for j in range(50)], 8)),
        ["w1 w2 ."], vocab, hps) for _ in range(hps.batch_size)]
    batch = Batch(exs, hps, vocab)
    state = trainer.init_train_state(hps, vocab.size(), seed=0)
    plan = mesh_lib.make_mesh(hps)
    sharded = mesh_lib.shard_train_state(plan, state)
    step = mesh_lib.make_sharded_train_step(plan, donate=False)
    losses = []
    for _ in range(3):
        sharded, metrics = step(sharded, batch.as_arrays())
        losses.append(float(metrics.loss))
    assert all(np.isfinite(losses)), losses
    emb = sharded.params["embedding"]
    assert emb.sharding.spec == mesh_lib.P("tp", None), emb.sharding
    acc = jax.tree_util.tree_leaves(sharded.opt_state.accumulators)[0]
    assert acc.dtype == jnp.bfloat16, acc.dtype
    print(f"mesh train smoke OK: dp=4 x tp=2, bf16 wire + loss_chunk + "
          f"bf16 opt state, 3 steps, losses {['%.3f' % x for x in losses]}")


def serve_smoke() -> None:
    rows = [(f"uuid-{i}", f"article {i} .", "", f"reference {i} .")
            for i in range(8)]
    # 12 words + 4 specials = 16 ids: divisible by tp=2
    vocab = Vocab(words=["article", "reference", ".", "0", "1", "2", "3",
                         "4", "5", "6", "7", "x"])
    assert vocab.size() % 2 == 0, vocab.size()
    base = HParams(mode="decode", batch_size=2, hidden_dim=16, emb_dim=8,
                   vocab_size=vocab.size(), max_enc_steps=16,
                   max_dec_steps=6, beam_size=2, min_dec_steps=1,
                   max_oov_buckets=4, serve_max_wait_ms=50.0,
                   serve_max_queue=32)
    params = trainer.init_train_state(base, vocab.size(), seed=0).params

    def run(hps, tag):
        server = ServingServer(
            hps, vocab, params=params,
            decode_root=tempfile.mkdtemp(prefix=f"mesh_smoke_{tag}_"))
        sink = CollectionSink()
        with server:
            server.serve(CollectionSource(rows), sink)
        assert len(sink.rows) == 8, (tag, sink.rows)
        return {r[0]: r for r in sink.rows}

    want = run(base, "single")
    got_mb = run(base.replace(dp=2, tp=2), "mesh_microbatch")
    assert got_mb == want, "sharded micro-batch rows drifted"
    got_c = run(base.replace(dp=2, tp=2, serve_mode="continuous",
                             serve_slots=2, serve_refill_chunk=2),
                "mesh_continuous")
    assert got_c == want, "sharded continuous rows drifted"
    print("mesh serve smoke OK: dp=2 x tp=2 micro-batch AND continuous "
          "rows identical to single-device (8 rows each)")


def main() -> None:
    n = len(jax.devices())
    assert n >= 8, (
        f"mesh smoke needs the faked 8-device CPU mesh, have {n} "
        f"(export XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    train_smoke()
    serve_smoke()


if __name__ == "__main__":
    from textsummarization_on_flink_tpu.utils import (
        set_default_compile_cache,
    )

    set_default_compile_cache()
    main()
