"""Speculative-tier smoke (ISSUE 10): bootstrap an AAN draft from a
tiny transformer's own params (the `spec_draft="map"` recipe), run the
draft-then-verify fast path through the REAL decoder's tier surface,
and assert token exactness against the greedy tier — the no-hardware
proof that draft init -> spec decode -> verify works end to end.
Wired into scripts/repro.sh.

``--distill`` (ISSUE 12) runs the distilled-narrow-draft flow instead:
train a tiny teacher a few steps on synthetic copy data, distill a
NARROW draft (draft_hidden < H, factored vocab head) from its greedy
outputs through train/distill.DistillTrainer, then spec-decode under
the acceptance-adaptive controller and assert token exactness vs
greedy — the no-hardware proof that distill -> narrow spec ->
adaptive-k works end to end (the committed acceptance floor lives in
BYTE_BUDGET.json spec.distill, enforced by tests/test_distill.py).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import tempfile  # noqa: E402

import jax  # noqa: E402

from textsummarization_on_flink_tpu import obs  # noqa: E402
from textsummarization_on_flink_tpu.config import HParams  # noqa: E402
from textsummarization_on_flink_tpu.data.batching import (  # noqa: E402
    Batch,
    SummaryExample,
)
from textsummarization_on_flink_tpu.data.vocab import Vocab  # noqa: E402
from textsummarization_on_flink_tpu.decode.decoder import (  # noqa: E402
    BeamSearchDecoder,
)
from textsummarization_on_flink_tpu.models import get_family  # noqa: E402


def main() -> None:
    vocab = Vocab(words=["article", "reference", ".", "0", "1", "2", "3",
                         "4", "5", "6", "7"])
    hps = HParams(mode="decode", batch_size=4, hidden_dim=16, emb_dim=16,
                  vocab_size=vocab.size(), max_enc_steps=16,
                  max_dec_steps=8, beam_size=2, min_dec_steps=1,
                  max_oov_buckets=4, model_family="transformer",
                  num_heads=2, enc_layers=1, dec_layers=2,
                  spec_k=3, draft_dec_layers=1, spec_draft="map")
    hps.validate()
    params = get_family(hps.model_family).init_params(
        hps, vocab.size(), jax.random.PRNGKey(0))
    # the decoder builds the mapped draft itself (spec_draft="map")
    decoder = BeamSearchDecoder(
        hps, vocab, batcher=None, params=params,
        decode_root=tempfile.mkdtemp(prefix="spec_smoke_"))
    assert decoder.has_draft, "mapped draft bootstrap failed"

    examples = [SummaryExample.build(f"article {i} .", [], vocab, hps,
                                     uuid=f"uuid-{i}") for i in range(4)]
    batch = Batch(examples, hps, vocab)
    greedy = decoder.decode_batch(batch, tier="greedy")
    spec = decoder.decode_batch(batch, tier="spec")
    draft = decoder.decode_batch(batch, tier="draft")
    assert len(spec) == len(greedy) == len(draft) == 4
    for g, s in zip(greedy, spec):
        assert g.decoded_words == s.decoded_words, (
            f"spec tier drifted from greedy for {g.uuid}: "
            f"{g.decoded_words} vs {s.decoded_words}")
        assert s.tier == "spec"
    reg = obs.registry()
    cycles = int(reg.counter("decode/spec_cycles_total").value)
    drafted = int(reg.counter("decode/spec_draft_tokens_total").value)
    accepted = int(reg.counter("decode/spec_accepted_tokens_total").value)
    rate = accepted / drafted if drafted else 0.0
    print(f"spec smoke OK: 4 rows token-exact with greedy; "
          f"{cycles} verify cycle(s), acceptance {accepted}/{drafted} "
          f"({rate:.0%}); draft tier served {len(draft)} rows")


def distill_main() -> None:
    """The ISSUE-12 smoke: synthetic distillation of the narrow draft,
    then adaptive spec decode, token-exact with greedy."""
    import numpy as np  # noqa: E402

    from textsummarization_on_flink_tpu.config import (  # noqa: E402
        derive_draft_hps,
    )
    from textsummarization_on_flink_tpu.decode import (  # noqa: E402
        beam_search,
        speculative,
    )
    from textsummarization_on_flink_tpu.models import (  # noqa: E402
        avg_attention,
    )
    from textsummarization_on_flink_tpu.train import (  # noqa: E402
        distill,
        trainer as trainer_lib,
    )
    from tests.test_distill import (  # noqa: E402
        _ArraysBatch,
        _CycleBatcher,
        copy_task_arrays,
    )
    from tests.test_speculative import make_arrays  # noqa: E402

    hps = HParams(batch_size=4, hidden_dim=16, emb_dim=16, vocab_size=32,
                  max_enc_steps=12, max_dec_steps=8, beam_size=1,
                  min_dec_steps=2, max_oov_buckets=4, mode="decode",
                  model_family="transformer", num_heads=2, enc_layers=1,
                  dec_layers=2, spec_k=2, draft_dec_layers=1,
                  draft_hidden=8, draft_vocab_rank=4,
                  spec_k_adaptive=True, spec_k_min=1, spec_k_max=5)
    hps.validate()
    # a teacher with LEARNABLE greedy behavior: a few hundred steps of
    # the synthetic copy task (the pointer mechanism's native move)
    thps = hps.replace(mode="train")
    tstate = trainer_lib.init_train_state(thps, hps.vocab_size, seed=0)
    tstep = jax.jit(trainer_lib.make_train_step(thps))
    tdata = [copy_task_arrays(make_arrays(hps, 4, seed=1000 + s), hps)
             for s in range(8)]
    for i in range(200):
        tstate, _ = tstep(tstate, tdata[i % 8])
    teacher = jax.device_get(tstate.params)

    dhps = derive_draft_hps(hps)
    fresh = avg_attention.init_params(dhps, hps.vocab_size,
                                      jax.random.PRNGKey(7))
    held = make_arrays(hps, 4, seed=100)
    before = distill.acceptance_rate(teacher, fresh, hps, held)

    batches = [_ArraysBatch(make_arrays(hps, 4, seed=s)) for s in range(8)]
    dt = distill.DistillTrainer(hps, hps.vocab_size,
                                _CycleBatcher(batches), teacher,
                                cache_teacher=True, seed=7)
    dt.distill(200)
    draft = jax.device_get(dt.draft_params())
    after = distill.acceptance_rate(teacher, draft, hps, held)

    ctl = speculative.SpecKController.from_hps(hps)
    out = speculative.run_spec_decode(teacher, draft, hps, held,
                                      controller=ctl)
    greedy = beam_search.run_beam_search(teacher, hps.replace(beam_size=1),
                                         held)
    for b in range(4):
        n = int(greedy.length[b])
        got = list(np.asarray(out.tokens[b])[:n])
        want = list(np.asarray(greedy.tokens[b])[:n])
        assert got == want, (
            f"distilled adaptive spec drifted from greedy on held-out "
            f"row {b}: {got} vs {want}")
    assert after > before, (
        f"distillation did not raise held-out acceptance "
        f"({before:.3f} -> {after:.3f})")
    print(f"distill-spec smoke OK: held-out acceptance "
          f"{before:.2f} -> {after:.2f} after 200 distill steps; "
          f"adaptive spec_k ended at k={ctl.k} "
          f"(mean {ctl.mean_k:.2f} over {ctl.cycles} cycles), "
          f"4 rows token-exact with greedy")


if __name__ == "__main__":
    from textsummarization_on_flink_tpu.utils import (
        set_default_compile_cache,
    )

    set_default_compile_cache()
    if "--distill" in sys.argv[1:]:
        distill_main()
    else:
        main()
