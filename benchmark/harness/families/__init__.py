"""One module a model family, found by a configuration's "family" key
(`reference.family(name)` imports `harness.families.<name>`).  Everything
the harness knows about a family lives in its module; nothing outside
this directory, the benchmark's tests included, branches on a family's,
a configuration's or a cell's name.  A module imports nothing of the
program and holds:

  param_specs(hp)      {leaf path: (shape, init kind)} in the program's
                       parameter layout.  weights.py draws the kinds:
                       `matrix` / `lstm` / `vocab` normal(0, gain /
                       sqrt(shape[0])); `stacked`, for experts or layers
                       stacked on leading axes, normal(0, gain /
                       sqrt(shape[-2])); `embedding` / `tied_embedding`,
                       `vector`, `vocab_bias`, `ones`, `zeros`
  encode, decode,      the plain reference (reference.py has what the
  LOG_EPS              families share: mixture, loss, Adagrad, beam search):
                       encode(p, hp, ids, n, act), decode(p, hp, enc,
                       dec_inputs, decode_mode, act)
  token_logprobs,      optional, in place of reference.py's pointer
  next_dist            mixture, for a family with no copy distribution or
                       a head of its own, one article at a time:
                       token_logprobs(p, hp, ids, ext_ids, n, dec_inputs,
                       targets, decode_mode, act) -> log P(targets) [Td];
                       next_dist(p, hp, ids, ext_ids, n, dec_inputs, t,
                       act) -> the distribution of the token after
                       position t
  forward_macs_per_row, decode_step_macs_per_hyp, beam_state_bytes,
  enc_view_bytes, prefill_macs_and_weights
                       what counts.py's train_step / slot_chunk / prefill
                       are composed of (`counts.prefill` reads
                       hp["hidden_dim"]: a family names its width that)
  count_<name>(hp, dep, ctx)
                       any further count a metric file may name
                       (readers.py resolves a name it does not know here)
  wire, length_code,   optional: how seed-made weights end a summary at
  word_for_length,     the length the article's first word codes
  MID_CLOCK            (init.summary_clock); a configuration that asks
                       for a clock its family does not offer is an error.
                       MID_CLOCK is data for the tests: the `init` keys
                       (`stop_bias`, `summary_clock`) of the clock at
                       tests/tiny.py's middle size.  A family with no
                       unit that can count decode steps offers none of
                       the four; its configurations have no
                       `init.summary_clock`, its mixes no `summary` block,
                       and every summary it serves is `max_dec_steps` long

THE ACTIVATIONS' TYPE, apart from the parameters' stored type (the one
rule, kept here).  A plain reference is TOLD the type of its activations,
`act`, last on each of the four calls above; nothing infers it from a
leaf's dtype.  The parameters arrive in the type the configuration stores
them in (`param_dtype`), and each leaf is cast to `act` where it is read,
inside the jitted reference: at the matmul, the lookup (the rows looked
up, not the table) or the norm that reads it; never as a copy of the
tree, so a tree of 10 GB of bfloat16 leaves is never held as 20 GB of
float32.  What a family keeps in float32 whatever `act` is (softmax
inputs, a norm's statistics), it writes down in its own module.
  SOUND    float32 activations, for EVERY configuration: over a float32
           tree the cast is nothing, over a bfloat16 tree the leaves are
           widened where read and everything else is float32
  CONTROL  bfloat16 activations over leaves rounded to bfloat16: the
           nearest precision below what the configurations state.  A
           bfloat16 tree is handed in as stored; a float32 tree is
           rounded once BEFORE the program (`correct.low_precision`, a
           copy of half its size), because inside one program XLA may
           drop a float32 -> bfloat16 -> float32 round trip, and the
           cast where a leaf is read is then nothing
correct.py states one of the two on every call it makes, and a reference
function called with neither is sound.  A cell's `score_gap` limit stands
between the two: a program that lowers ITS activations must fail it, so
a cell whose control does not fail is a fault to mend in the family's
reference or in the limit, never a cell to park: a `model_config` PR that
holds its cell under benchmark/held/ or leaves its entries out of
BENCHMARK.json is refused whole (`config_not_added`: ledger, PR 35).  The
entries such a PR writes there are appended ones, and its cell's name
appended to the `workloads` lists of the metrics it reports (PERF.md
section 7).

What a configuration and a cell state of their own, beside the family:

  param_dtype          the type the parameters are made, stored and
                       served in (absent: float32).  A configuration of
                       bfloat16 parameters says so here, and under
                       `assumed` which activations the published model
                       keeps in float32 (residual stream, norms, softmax,
                       router), so that SOUND is what the source computes
  rehearse             sizes for `run.py --rehearse 1` and the CPU tests,
                       laid over benchmark/rehearse.json's: `hparams`
                       (the family's own widths, which the shared blocks
                       do not know: ranks, expert counts, a selection's k
                       small enough that it still cuts), `deployment`,
                       `init`.  The tests' shrinkers (tests/tiny.py
                       `tiny_config`, `mid_config`) cut the widths they
                       know and take `rehearse.hparams` for the rest
  check.sample         (the cell's file) how many finished requests the
                       reference scores (`score` >= 1, always) and how
                       many it searches itself (`beam`; 0 where one
                       cacheless search costs more than the window).
                       `correct.serve_numbers` gives `beam_gap` and
                       `beam_gap_median` only where `beam` >= 1, and the
                       cell's `limits` hold them if and only if it does
"""

import jax.numpy as jnp

SOUND = jnp.dtype(jnp.float32)
CONTROL = jnp.dtype(jnp.bfloat16)
