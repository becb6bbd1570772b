"""The plain references against the program on the CPU at a tiny size:
training loss, gradients, and beam search, on seed-made weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import reference as ref
from harness import weights
from tests.tiny import tiny_config


def _program_hps(cfg, batch):
    from textsummarization_on_flink_tpu.config import HParams

    return HParams(batch_size=batch, **cfg["hparams"])


def _arrays(cfg, rng, B):
    hp = cfg["hparams"]
    Te, Td, V = hp["max_enc_steps"], hp["max_dec_steps"], hp["vocab_size"]
    a = {"enc_batch": np.full((B, Te), 1, np.int32),
         "enc_batch_extend_vocab": np.full((B, Te), 1, np.int32),
         "enc_lens": np.zeros((B,), np.int32),
         "enc_padding_mask": np.zeros((B, Te), np.float32),
         "dec_batch": np.full((B, Td), 1, np.int32),
         "target_batch": np.full((B, Td), 1, np.int32),
         "dec_padding_mask": np.zeros((B, Td), np.float32)}
    for i in range(B):
        n = rng.randint(3, Te + 1)
        ids = rng.randint(4, V, size=n)
        ext = ids.copy()
        oov = rng.rand(n) < 0.2
        ext[oov] = V + rng.randint(0, 2, size=oov.sum())
        a["enc_batch"][i, :n] = np.where(oov, 0, ids)
        a["enc_batch_extend_vocab"][i, :n] = ext
        a["enc_lens"][i] = n
        a["enc_padding_mask"][i, :n] = 1
        d = rng.randint(2, Td + 1)
        tgt = rng.randint(4, V, size=d)
        tgt[0] = ext[0]  # a copyable target, possibly out of vocabulary
        a["dec_batch"][i, :d] = np.concatenate(
            [[2], np.where(tgt[:-1] >= V, 0, tgt[:-1])])
        a["target_batch"][i, :d] = tgt
        a["dec_padding_mask"][i, :d] = 1
    return a


@pytest.mark.parametrize("name", ["pg_see2017", "tf_cnndm"])
def test_loss_and_gradients_agree(name):
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib

    cfg = tiny_config(name)
    hps = _program_hps(cfg, 4)
    params = weights.make_params(cfg, 5)
    arrays = _arrays(cfg, np.random.RandomState(0), 4)
    grads, (loss, _, _) = trainer_lib.make_grad_fn(hps)(
        params, {k: jnp.asarray(v) for k, v in arrays.items()})
    fam = ref.family(cfg["family"])
    rloss, rgrads = ref.loss_and_grads(
        fam, params, cfg["hparams"],
        {k: jnp.asarray(v) for k, v in arrays.items()}, block=2)
    assert abs(float(loss) - float(rloss)) < 1e-5 * abs(float(rloss))
    for (path, g), r in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_leaves(rgrads)):
        scale = max(float(jnp.max(jnp.abs(r))), 1e-6)
        assert float(jnp.max(jnp.abs(g - r))) < 2e-4 * scale + 1e-7, path


@pytest.mark.parametrize("name", ["pg_see2017", "tf_cnndm"])
def test_beam_search_and_scores_agree(name):
    from textsummarization_on_flink_tpu.decode import beam_search

    cfg = tiny_config(name)
    cfg["init"]["stop_bias"] = 1.0
    hps = _program_hps(cfg, 4)
    params = weights.make_params(cfg, 7)
    arrays = _arrays(cfg, np.random.RandomState(1), 4)
    out = jax.device_get(beam_search.run_beam_search_jit(
        params, hps, {k: v for k, v in arrays.items()
                      if k.startswith("enc_")}, loop="scan"))
    fam = ref.family(cfg["family"])
    arts, outs = [], []
    for b in range(4):
        n = int(arrays["enc_lens"][b])
        art = (arrays["enc_batch"][b, :n],
               arrays["enc_batch_extend_vocab"][b, :n])
        toks, avg = ref.beam_search(fam, params, cfg["hparams"], *art)
        served = [int(t) for t in out.tokens[b][1:int(out.length[b])]]
        assert abs(avg - float(out.avg_log_prob[b])) < 1e-4 * abs(avg), b
        assert toks == served, b
        arts.append(art)
        outs.append(served)
    scores = ref.score_tokens(fam, params, cfg["hparams"], arts, outs)
    for b in range(4):
        assert abs(scores[b] / int(out.length[b])
                   - float(out.avg_log_prob[b])) < 1e-4
