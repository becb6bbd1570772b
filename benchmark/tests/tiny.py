"""Tiny configurations for the CPU tests: every size cut, never used on
the chip; and the cells, as the tests read them out of BENCHMARK.json."""
import json
import os

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY = {"hidden_dim": 16, "emb_dim": 8, "vocab_size": 64,
        "max_enc_steps": 12, "max_dec_steps": 8, "beam_size": 2,
        "min_dec_steps": 2, "max_oov_buckets": 4, "num_heads": 2,
        "ffn_dim": 32, "enc_layers": 2, "dec_layers": 2}
# the middle size: what the control tests (test_control.py) run the plain
# reference at, wide enough for bfloat16 to part from float32
MID = {"hidden_dim": 64, "emb_dim": 32, "vocab_size": 2000,
       "max_enc_steps": 64, "max_dec_steps": 24, "beam_size": 2,
       "min_dec_steps": 4, "max_oov_buckets": 8, "num_heads": 4,
       "ffn_dim": 128, "enc_layers": 2, "dec_layers": 2}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _shrink(hp, sizes):
    hp.update({k: v for k, v in sizes.items() if k in hp})


def tiny_config(name: str):
    """The configuration with TINY over the keys it has, then its own
    `rehearse.hparams` (as `run.apply_rehearsal` takes them): a family
    with widths of its own shrinks them in its own file."""
    cfg = _load(BENCH, "configs", f"{name}.json")
    _shrink(cfg["hparams"], TINY)
    cfg["hparams"].update(cfg.get("rehearse", {}).get("hparams", {}))
    return cfg


def mid_config(name: str):
    """The tiny configuration with MID over the keys it has: the widths
    TINY and MID do not know stay at their rehearsal size."""
    cfg = tiny_config(name)
    _shrink(cfg["hparams"], MID)
    return cfg


def mid_batches(hp, rows=8, steps=3):
    """`steps` training batches of `rows` full-length rows at the sizes
    of `hp`, every other target copied from its article: what the
    training control and the reference's record are read on."""
    import numpy as np

    rng = np.random.RandomState(0)
    B, Te, Td, V = rows, hp["max_enc_steps"], hp["max_dec_steps"], \
        hp["vocab_size"]
    batches = []
    for _ in range(steps):
        ids = rng.randint(4, V, (B, Te)).astype(np.int32)
        tgt = rng.randint(4, V, (B, Td)).astype(np.int32)
        tgt[:, ::2] = ids[:, :Td:2][:, :tgt[:, ::2].shape[1]]
        batches.append({
            "enc_batch": ids, "enc_batch_extend_vocab": ids,
            "enc_lens": np.full((B,), Te, np.int32),
            "enc_padding_mask": np.ones((B, Te), np.float32),
            "dec_batch": np.concatenate(
                [np.full((B, 1), 2, np.int32), tgt[:, :-1]], 1),
            "target_batch": tgt,
            "dec_padding_mask": np.ones((B, Td), np.float32)})
    return batches


def held_cells():
    """The held cells' files (benchmark/held/*.json), in name order."""
    import glob

    return [_load(p) for p in sorted(glob.glob(
        os.path.join(BENCH, "held", "*.json")))]


def _entries(held):
    return ([h["workload"] for h in held_cells()] if held
            else _load(ROOT, "BENCHMARK.json")["workloads"])


def mix_file(traffic_name):
    return _load(BENCH, "traffic", traffic_name + ".json")


def cell_file(cell):
    return _load(BENCH, "workloads", cell + ".json")


def cells(kind, held=False):
    """(cell, configuration) of BENCHMARK.json's cells (or of the held
    cells, benchmark/held/) whose mix is of `kind`."""
    return [(w["name"], w["config"]) for w in _entries(held)
            if mix_file(w["traffic"])["kind"] == kind]


def traffic_of(cell):
    """The traffic file of a cell, BENCHMARK.json's or held."""
    return mix_file({w["name"]: w["traffic"] for w in
                     _entries(False) + _entries(True)}[cell])


def benchmark_with_held(tmp_dir) -> str:
    """A BENCHMARK.json in tmp_dir holding the real entries and those of
    the held cells (benchmark/held/*.json), so that the code a held cell
    runs stays under test.  Returns its path."""
    b = _load(ROOT, "BENCHMARK.json")
    for h in held_cells():
        b["configs"].append(h["config"])
        b["workloads"].append(h["workload"])
        b["end_to_end"][-1:-1] = h["end_to_end"]
        b["per_layer"].extend(h["per_layer"])
    out = os.path.join(str(tmp_dir), "BENCHMARK.json")
    with open(out, "w") as f:
        json.dump(b, f)
    return out
