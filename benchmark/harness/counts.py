"""The operations and the least bytes the ALGORITHM needs, from shapes,
whatever implements it.  Each function takes the config file's "hparams"
and the cell's deployment numbers and returns {"flops": ..., "bytes": ...}
for ONE call of the named program.  The train-step FLOP counts are copies
of bench.py's `train_flops_per_step` / `transformer_flops_per_step`
(checked equal in benchmark/tests); the byte counts and the decode counts
are this benchmark's own.

Bytes are the least an implementation must move between HBM and the
chip once: weights read once, the resident state the step must read and
write, inputs and outputs.  Intermediate tensors are not counted (a
fused implementation need not write them), so a roofline share from
these counts cannot be inflated by materialised temporaries.
"""

from __future__ import annotations

from typing import Any, Dict

F32 = 4


def _dims(hp):
    return (int(hp["hidden_dim"]), int(hp["vocab_size"]),
            int(hp["max_enc_steps"]), int(hp["max_dec_steps"]))


def n_params(hp) -> int:
    from harness import weights

    return weights.n_params(weights.param_specs(hp))


# ------------------------------------------------------------- training

def _pg_forward_macs_per_row(hp, Te, Td) -> float:
    H, V, _, _ = _dims(hp)
    E, D = int(hp["emb_dim"]), 2 * H
    enc_lstm = 2 * Te * (E + H) * 4 * H
    reduce_states = 2 * D * H
    enc_feats = Te * D * D
    dec_per_step = ((E + D) * E + (E + H) * 4 * H + D * D + Te * D + Te * D
                    + (2 * D + E) + (H + D) * H + H * V)
    return enc_lstm + reduce_states + enc_feats + Td * dec_per_step


def _tf_layer_macs(hp, Te, Td):
    H = int(hp["hidden_dim"])
    F = int(hp.get("ffn_dim") or 4 * H)
    enc_layer = 4 * Te * H * H + 2 * Te * Te * H + 2 * Te * H * F
    dec_layer = (4 * Td * H * H + 2 * Td * Td * H + 2 * Td * H * H
                 + 2 * Te * H * H + 2 * Td * Te * H + 2 * Td * H * F)
    return enc_layer, dec_layer


def _tf_forward_macs_per_row(hp, Te, Td) -> float:
    H, V, _, _ = _dims(hp)
    enc_layer, dec_layer = _tf_layer_macs(hp, Te, Td)
    return (int(hp["enc_layers"]) * enc_layer
            + int(hp["dec_layers"]) * dec_layer + Td * H * V)


def _forward_macs_per_row(hp, Te, Td) -> float:
    if hp["model_family"] == "transformer":
        return _tf_forward_macs_per_row(hp, Te, Td)
    return _pg_forward_macs_per_row(hp, Te, Td)


def train_step(hp: Dict[str, Any], dep: Dict[str, Any]) -> Dict[str, float]:
    """One optimizer step of `batch_size` rows at full encoder and decoder
    length: forward + backward = 3 x forward, 2 FLOPs a MAC.  Bytes:
    parameters read, gradients written and read, Adagrad accumulator read
    and written, parameters written (6 passes over the parameters), plus
    the batch's ids."""
    _, _, Te, Td = _dims(hp)
    B = int(dep["batch_size"])
    flops = 3 * 2 * B * _forward_macs_per_row(hp, Te, Td)
    nbytes = 6 * n_params(hp) * F32 + B * (3 * Te + 3 * Td) * 4
    return {"flops": float(flops), "bytes": float(nbytes)}


# -------------------------------------------------------------- serving

def _beam_state_bytes(hp) -> int:
    """One resident's per-hypothesis decode state that a step reads and
    writes: the LSTM (c, h), or the self-attention K/V cache."""
    H, _, _, Td = _dims(hp)
    K = int(hp["beam_size"])
    if hp["model_family"] == "transformer":
        return K * int(hp["dec_layers"]) * (Td + 1) * H * 2 * F32
    return K * 2 * H * F32


def _enc_view_bytes(hp, Te) -> int:
    """One resident's encoder view that every decode step reads: encoder
    states and features, or the per-layer cross-attention K/V."""
    H = int(hp["hidden_dim"])
    if hp["model_family"] == "transformer":
        return int(hp["dec_layers"]) * Te * H * 2 * F32
    return Te * 2 * H * 2 * F32


def _decode_step_macs_per_hyp(hp, Te, t) -> float:
    """One decode step for one hypothesis at decode position t over an
    article of Te tokens."""
    H, V, _, _ = _dims(hp)
    if hp["model_family"] == "transformer":
        F = int(hp.get("ffn_dim") or 4 * H)
        layer = (4 * H * H + 2 * (t + 1) * H + 2 * H * H + 2 * Te * H
                 + 2 * H * F)
        return int(hp["dec_layers"]) * layer + H * V + 2 * H
    E, D = int(hp["emb_dim"]), 2 * H
    # decode mode attends twice a step (the previous context is rebuilt)
    return ((E + D) * E + (E + H) * 4 * H + 2 * (D * D + 2 * Te * D)
            + (2 * D + E) + (H + D) * H + H * V)


def slot_chunk(hp: Dict[str, Any], dep: Dict[str, Any],
               occupied: float, mean_len: float) -> Dict[str, float]:
    """One call of the slot-step program: `chunk` decode steps.  The
    ALGORITHM's work is that of the OCCUPIED residents (`occupied`, the
    mean over the window, from the scheduler's own histogram) at their
    mean article length; steps computed for empty slots are not work.
    Bytes a step: the parameters once, and each occupied resident's
    encoder view and beam state."""
    _, _, _, Td = _dims(hp)
    K, chunk = int(hp["beam_size"]), int(dep["chunk"])
    macs = occupied * K * _decode_step_macs_per_hyp(hp, mean_len, Td / 2)
    nbytes = (n_params(hp) * F32 + occupied * (
        _enc_view_bytes(hp, mean_len) + 2 * _beam_state_bytes(hp)))
    return {"flops": float(2 * macs * chunk), "bytes": float(nbytes * chunk)}


def prefill(hp: Dict[str, Any], dep: Dict[str, Any],
            mean_len: float) -> Dict[str, float]:
    """One call of the prefill program for one article of mean_len
    tokens: the encoder, and the encoder view it leaves behind."""
    H = int(hp["hidden_dim"])
    if hp["model_family"] == "transformer":
        enc_layer, _ = _tf_layer_macs(hp, mean_len, 0)
        macs = (int(hp["enc_layers"]) * enc_layer
                + int(hp["dec_layers"]) * 2 * mean_len * H * H)
        w = (int(hp["enc_layers"]) * (4 * H * H + 2 * H * int(
            hp.get("ffn_dim") or 4 * H))
             + int(hp["dec_layers"]) * 2 * H * H)
    else:
        E, D = int(hp["emb_dim"]), 2 * H
        macs = (2 * mean_len * (E + H) * 4 * H + 2 * D * H
                + mean_len * D * D)
        w = 2 * (E + H) * 4 * H + 2 * D * H + D * D
    nbytes = w * F32 + mean_len * H * F32 + _enc_view_bytes(hp, mean_len)
    return {"flops": float(2 * macs), "bytes": float(nbytes)}
