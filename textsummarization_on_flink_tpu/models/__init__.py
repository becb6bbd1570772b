"""Model families: pointer-generator (LSTM seq2seq) and transformer.

Every family is a module exposing the same functional surface, so the
Trainer/Evaluator, beam search, checkpointing, and serving stack are
family-agnostic:

  init_params(hps, vsize, key) -> Params
  forward_train(params, hps, arrays) -> TrainOutput
  beam_encode(params, hps, arrays) -> per-batch encoder view (pytree)
  beam_head(params, hps, ext_ids) -> the output head at the articles'
      ids, gathered once a decode loop (or None: ops/topk.head_at)
  beam_adapter(hps) -> (init_state, step) beam-search closures

Select with ``hps.model_family`` (the reference has a single hardcoded
model, run_summarization.py:376; the family seam is a rebuild addition
for the second, transformer family).

The third family, ``avg_attention``, is the speculative tier's draft
(O(1)-in-history decode state); it honors two extra HParams the other
families ignore — ``draft_hidden`` (narrow decoder behind boundary
projections) and ``draft_vocab_rank`` (factored vocab head) — while
keeping this exact functional surface, so every consumer listed above
works on the narrow variant unmodified (ISSUE 12).
"""

from __future__ import annotations

from types import ModuleType

FAMILIES = ("pointer_generator", "transformer", "avg_attention")


def masked_adapter(beam_adapter_fn):
    """Derive a family's ``beam_adapter_masked`` from its
    ``beam_adapter`` (the length-masked slot-decode protocol, ISSUE 11):
    the same step with an explicit leading ``nb`` (traced active-block
    count) argument, which step_slots_jit binds from the residents'
    valid lengths.  ONE wrapper — the calling convention lives here, so
    a future change to the masked-step signature lands in one place for
    every family."""

    def beam_adapter_masked(hps):
        init_state, step = beam_adapter_fn(hps)

        def step_masked(params, enc_one, enc_mask, ext_ids, nb, t, latest,
                        state, head=None):
            return step(params, enc_one, enc_mask, ext_ids, t, latest,
                        state, nb=nb, head=head)

        return init_state, step_masked

    return beam_adapter_masked


def get_family(name: str) -> ModuleType:
    """Resolve a model-family name to its module (lazy imports keep
    startup light and avoid cycles)."""
    if name == "pointer_generator":
        from textsummarization_on_flink_tpu.models import pointer_generator
        return pointer_generator
    if name == "transformer":
        from textsummarization_on_flink_tpu.models import transformer
        return transformer
    if name == "avg_attention":
        from textsummarization_on_flink_tpu.models import avg_attention
        return avg_attention
    raise ValueError(
        f"unknown model_family {name!r}; expected one of {FAMILIES}")
