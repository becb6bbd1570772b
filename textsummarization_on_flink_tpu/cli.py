"""Command-line entry: the reference's run_summarization.py surface.

Dispatch parity with /root/reference/src/main/python/pointer-generator/
run_summarization.py `main` (:333-367):

  * checkpoint-surgery flags run-and-exit: --convert_to_coverage_model
    (:157-178), --restore_best_model (:132-154);
  * --inference=1: decode raw text files (RawTextBatcher path, :339-348);
  * --mode=train: Batcher over chunk files + training loop with 60s
    checkpointing (:351-356, Supervisor save_model_secs);
  * --mode=eval: reload-latest-checkpoint eval loop with running-average
    loss and best-model saving (:357-359 -> :247-292);
  * --mode=decode: beam-search decode, ROUGE when --single_pass (:360-365).

Flags are the reference's 23 names via HParams.from_argv (config.py); the
seed matches tf.set_random_seed(111) (:329).

Usage:
    python -m textsummarization_on_flink_tpu --mode=train \
        --data_path=.../train_* --vocab_path=.../vocab \
        --log_root=/tmp/log --exp_name=myexperiment
"""

from __future__ import annotations

import logging
import os
import sys
from typing import List, Optional

from textsummarization_on_flink_tpu.checkpoint import checkpointer as ckpt_lib
from textsummarization_on_flink_tpu.config import HParams
from textsummarization_on_flink_tpu.data.batcher import Batcher
from textsummarization_on_flink_tpu.data.etl import raw_text_example_source
from textsummarization_on_flink_tpu.data.vocab import Vocab
from textsummarization_on_flink_tpu.decode.decoder import BeamSearchDecoder
from textsummarization_on_flink_tpu.train import trainer as trainer_lib
from textsummarization_on_flink_tpu.utils import set_default_compile_cache

log = logging.getLogger(__name__)


def _dirs(hps: HParams):
    root = os.path.join(hps.log_root or ".", hps.exp_name or "exp")
    return root, os.path.join(root, "train"), os.path.join(root, "eval")


def setup_training(hps: HParams, vocab: Vocab,
                   batcher: Optional[Batcher] = None) -> trainer_lib.TrainState:
    """run_summarization.py:181-209: restore-or-init, train with periodic
    checkpoints (save_model_secs=60 parity)."""
    from textsummarization_on_flink_tpu.parallel import distributed

    from textsummarization_on_flink_tpu.utils import local_batch_hps

    _, train_dir, _ = _dirs(hps)
    # multi-host: the batcher feeds THIS host's shard of the global batch
    batcher = batcher or Batcher(hps.data_path, vocab, local_batch_hps(hps),
                                 single_pass=hps.single_pass)
    # Checkpointer.save is collective-then-chief-writes, so every host
    # holds one (the reference's is_chief MonitoredTrainingSession role,
    # train.py:74-81, applies to the WRITE inside save); every host also
    # restores so a resumed run starts from the same step.
    checkpointer = ckpt_lib.Checkpointer(train_dir, hps=hps)
    if distributed.is_chief():
        # embedding-projector metadata (model.py:185-197, data.py:93-105)
        vocab.write_metadata(os.path.join(train_dir, "vocab_metadata.tsv"))
    state = checkpointer.restore()
    if state is not None:
        log.info("restored training from step %d", int(state.step))
    trainer = trainer_lib.Trainer(hps, vocab.size(), batcher, state=state,
                                  checkpointer=checkpointer,
                                  train_dir=train_dir)
    return trainer.train(num_steps=hps.num_steps)


def run_eval(hps: HParams, vocab: Vocab, max_iters: int = 0,
             batcher: Optional[Batcher] = None) -> float:
    """run_summarization.py:247-292: each iteration loads the newest train
    checkpoint, evaluates one batch, updates the smoothed loss, and saves
    `bestmodel` on improvement.  max_iters=0 runs forever (reference
    behavior); tests pass a bound."""
    from textsummarization_on_flink_tpu.utils import local_batch_hps

    eval_hps = hps.replace(mode="eval")
    _, train_dir, eval_dir = _dirs(hps)
    batcher = batcher or Batcher(hps.data_path, vocab,
                                 local_batch_hps(eval_hps),
                                 single_pass=False)
    evaluator = trainer_lib.Evaluator(
        eval_hps, vocab.size(), batcher, eval_dir=eval_dir,
        best_saver=ckpt_lib.BestModelSaver(eval_dir))
    iters = 0
    while True:
        path, flat = ckpt_lib.load_ckpt(train_dir)
        state = ckpt_lib.arrays_to_state(flat)
        log.info("evaluating checkpoint %s (step %d)", path, int(state.step))
        evaluator.run(state.params, int(state.step), max_batches=1)
        iters += 1
        if max_iters and iters >= max_iters:
            return evaluator.running_avg_loss


def run_decode(hps: HParams, vocab: Vocab,
               batcher: Optional[Batcher] = None):
    """run_summarization.py:360-365 (+ raw-text inference :339-348)."""
    decode_hps = hps.replace(mode="decode")
    if batcher is None:
        if hps.inference:
            # Deliberate divergence: the reference keeps the process alive
            # after a non-single_pass raw-text run drains its (finite) file
            # glob, blocked forever in next_batch (batcher.py:382-395 ends
            # the fill thread without marking completion).  We treat the
            # glob as one bounded pass and exit cleanly either way.
            batcher = Batcher("", vocab, decode_hps, single_pass=True,
                              example_source=raw_text_example_source(
                                  hps.data_path))
        else:
            # The reference repeats ONE article across the batch because
            # its beam occupies the batch axis (run_summarization.py:312,
            # batcher.py:344-347).  Our beam search carries its own beam
            # axis, so a decode batch holds batch_size DISTINCT articles —
            # same per-article results, batch_size x the throughput.
            batcher = Batcher(hps.data_path, vocab, decode_hps,
                              single_pass=hps.single_pass,
                              decode_batch_mode="distinct")
    _, train_dir, _ = _dirs(hps)
    decoder = BeamSearchDecoder(decode_hps, vocab, batcher,
                                train_dir=train_dir)
    return decoder.decode(
        with_rouge=hps.single_pass and not hps.inference)


def main(argv: Optional[List[str]] = None) -> int:
    set_default_compile_cache()
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")
    argv = argv if argv is not None else sys.argv[1:]
    if argv and argv[0] == "serve-replica":
        # the process-fleet child entry (ISSUE 17; SERVING.md "Process
        # fleet"): config arrives as TS_HPS_JSON, ports leave through
        # the portfile handshake — not a flags-surface mode, so it
        # dispatches before the reference's 23-flag parse
        from textsummarization_on_flink_tpu.serve import procfleet

        return procfleet.replica_child_main(argv[1:])
    hps = HParams.from_argv(argv)
    hps.validate()
    log.info("Starting summarization in %s mode...", hps.mode)
    from textsummarization_on_flink_tpu.utils import apply_debug_mode

    apply_debug_mode(hps)  # --debug -> jax_debug_nans (ref :216-218)

    # surgery flags run-and-exit (:341-349 equivalents)
    _, train_dir, eval_dir = _dirs(hps)
    if hps.convert_to_coverage_model:
        ckpt_lib.convert_to_coverage_model(train_dir, hps, seed=hps.seed)
        return 0
    if hps.restore_best_model:
        ckpt_lib.restore_best_model(eval_dir, train_dir, hps)
        return 0

    vocab = Vocab(hps.vocab_path, hps.vocab_size)
    if hps.inference:
        run_decode(hps, vocab)
    elif hps.mode == "train":
        setup_training(hps, vocab)
    elif hps.mode == "eval":
        run_eval(hps, vocab)
    elif hps.mode == "decode":
        run_decode(hps, vocab)
    else:
        raise ValueError(
            "The 'mode' flag must be one of train/eval/decode")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
