"""Tiny configurations for the CPU tests: every size cut, never used on
the chip."""
import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {"hidden_dim": 16, "emb_dim": 8, "vocab_size": 64,
        "max_enc_steps": 12, "max_dec_steps": 8, "beam_size": 2,
        "min_dec_steps": 2, "max_oov_buckets": 4, "num_heads": 2,
        "ffn_dim": 32, "enc_layers": 2, "dec_layers": 2}


def tiny_config(name: str):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg = copy.deepcopy(cfg)
    for k, v in TINY.items():
        if k in cfg["hparams"]:
            cfg["hparams"][k] = v
    return cfg


ROOT = os.path.dirname(BENCH)


def benchmark_with_held(tmp_dir) -> str:
    """A BENCHMARK.json in tmp_dir holding the real entries and those of
    the held cells (benchmark/held/*.json), so that the code a held cell
    runs stays under test.  Returns its path."""
    import glob

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    for path in sorted(glob.glob(os.path.join(BENCH, "held", "*.json"))):
        with open(path) as f:
            h = json.load(f)
        b["configs"].append(h["config"])
        b["workloads"].append(h["workload"])
        b["end_to_end"][-1:-1] = h["end_to_end"]
        b["per_layer"].extend(h["per_layer"])
    out = os.path.join(str(tmp_dir), "BENCHMARK.json")
    with open(out, "w") as f:
        json.dump(b, f)
    return out
