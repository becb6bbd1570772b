"""The operations and the least bytes the ALGORITHM needs, from shapes,
whatever implements it.  Each function takes the family module
(harness/families/: what a count is composed of is the family's), the
config file's "hparams" and the cell's deployment numbers and returns
{"flops": ..., "bytes": ...} for ONE call of the named program.  The
train-step FLOP counts are copies of bench.py's `train_flops_per_step` /
`transformer_flops_per_step` (checked equal in benchmark/tests); the byte
counts and the decode counts are this benchmark's own.  A parameter takes
`dep["param_bytes"]` bytes (the configuration's `param_dtype`; 4 where
the deployment does not say).

Bytes are the least an implementation must move between HBM and the
chip once: weights read once, the resident state the step must read and
write, inputs and outputs.  Intermediate tensors are not counted (a
fused implementation need not write them), so a roofline share from
these counts cannot be inflated by materialised temporaries.
"""

from __future__ import annotations

from typing import Any, Dict

F32 = 4


def _param_bytes(dep) -> int:
    return int(dep.get("param_bytes", F32))


def n_params(fam, hp) -> int:
    from harness import weights

    return weights.n_params(fam.param_specs(hp))


# ------------------------------------------------------------- training

def train_step(fam, hp: Dict[str, Any], dep: Dict[str, Any],
               ) -> Dict[str, float]:
    """One optimizer step of `batch_size` rows at full encoder and decoder
    length: forward + backward = 3 x forward, 2 FLOPs a MAC.  Bytes:
    parameters read, gradients written and read, Adagrad accumulator read
    and written, parameters written (6 passes over the parameters), plus
    the batch's ids."""
    Te, Td = int(hp["max_enc_steps"]), int(hp["max_dec_steps"])
    B = int(dep["batch_size"])
    flops = 3 * 2 * B * fam.forward_macs_per_row(hp, Te, Td)
    nbytes = 6 * n_params(fam, hp) * _param_bytes(dep) + B * (3 * Te + 3 * Td) * 4
    return {"flops": float(flops), "bytes": float(nbytes)}


# -------------------------------------------------------------- serving

def slot_chunk(fam, hp: Dict[str, Any], dep: Dict[str, Any],
               occupied: float, mean_len: float) -> Dict[str, float]:
    """One call of the slot-step program: `chunk` decode steps.  The
    ALGORITHM's work is that of the OCCUPIED residents (`occupied`, the
    mean over the window, from the scheduler's own histogram) at their
    mean article length; steps computed for empty slots are not work.
    Bytes a step: the parameters once, and each occupied resident's
    encoder view and beam state."""
    K, chunk = int(hp["beam_size"]), int(dep["chunk"])
    macs = occupied * K * fam.decode_step_macs_per_hyp(
        hp, mean_len, int(hp["max_dec_steps"]) / 2)
    nbytes = (n_params(fam, hp) * _param_bytes(dep) + occupied * (
        fam.enc_view_bytes(hp, mean_len) + 2 * fam.beam_state_bytes(hp)))
    return {"flops": float(2 * macs * chunk), "bytes": float(nbytes * chunk)}


def prefill(fam, hp: Dict[str, Any], dep: Dict[str, Any],
            mean_len: float) -> Dict[str, float]:
    """One call of the prefill program for one article of mean_len
    tokens: the encoder, and the encoder view it leaves behind."""
    macs, w = fam.prefill_macs_and_weights(hp, mean_len)
    nbytes = (w * _param_bytes(dep) + mean_len * int(hp["hidden_dim"]) * F32
              + fam.enc_view_bytes(hp, mean_len))
    return {"flops": float(2 * macs), "bytes": float(nbytes)}
