"""One module a model family, found by a configuration's "family" key
(`reference.family(name)` imports `harness.families.<name>`).  Everything
the harness knows about a family lives in its module; nothing outside
this directory branches on a family's name.  A module imports nothing of
the program and holds:

  param_specs(hp)      {leaf path: (shape, init kind)} in the program's
                       parameter layout (weights.py draws the kinds)
  encode, decode,      the plain reference (reference.py has what the
  LOG_EPS              families share: mixture, loss, Adagrad, beam search)
  token_logprobs,      optional, in place of reference.py's pointer
  next_dist            mixture, for a family with no copy distribution or
                       a head of its own, one article at a time:
                       token_logprobs(p, hp, ids, ext_ids, n, dec_inputs,
                       targets, decode_mode) -> log P(targets) [Td];
                       next_dist(p, hp, ids, ext_ids, n, dec_inputs, t)
                       -> the distribution of the token after position t
  forward_macs_per_row, decode_step_macs_per_hyp, beam_state_bytes,
  enc_view_bytes, prefill_macs_and_weights
                       what counts.py's train_step / slot_chunk / prefill
                       are composed of
  count_<name>(hp, dep, ctx)
                       any further count a metric file may name
                       (readers.py resolves a name it does not know here)
  wire, length_code,   optional: how seed-made weights end a summary at
  word_for_length      the length the article's first word codes
                       (init.summary_clock); a configuration that asks
                       for a clock its family does not offer is an error
"""
