"""Builder's tool (chip): answers PERF.md's open question on what
`memory_stats()["peak_bytes_in_use"]` counts, by printing it beside
`compiled.memory_analysis()` for one train step of a configuration, and
describes the planes and lines of a short profiler trace.

    python benchmark/tools/probe_memory.py <config> [out.json]
"""
import json
import os
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))


def main():
    import jax
    import numpy as np

    from harness import cells, trace, weights
    from textsummarization_on_flink_tpu.train import trainer as trainer_lib

    name = sys.argv[1]
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    hps = cells.program_hps(cfg, "train", mode="train")
    B, Te, Td = hps.batch_size, hps.max_enc_steps, hps.max_dec_steps
    rng = np.random.RandomState(0)
    arrays = {
        "enc_batch": rng.randint(4, 1000, (B, Te)).astype(np.int32),
        "enc_lens": np.full((B,), Te, np.int32),
        "enc_padding_mask": np.ones((B, Te), np.float32),
        "enc_batch_extend_vocab": rng.randint(4, 1000, (B, Te)).astype(np.int32),
        "dec_batch": rng.randint(4, 1000, (B, Td)).astype(np.int32),
        "target_batch": rng.randint(4, 1000, (B, Td)).astype(np.int32),
        "dec_padding_mask": np.ones((B, Td), np.float32)}
    dev = jax.devices()[0]
    out = {"device": dev.device_kind, "before": dev.memory_stats()}
    state = trainer_lib.init_train_state(
        hps, hps.vocab_size, params=weights.make_params(cfg, 1))
    step = jax.jit(trainer_lib.make_train_step(hps), donate_argnums=0)
    compiled = step.lower(state, arrays).compile()
    ma = compiled.memory_analysis()
    out["memory_analysis"] = {k: int(getattr(ma, k)) for k in (
        "temp_size_in_bytes", "argument_size_in_bytes",
        "output_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes") if hasattr(ma, k)}
    out["after_compile"] = dev.memory_stats()
    log = tempfile.mkdtemp(prefix="probe-")
    state, m = compiled(state, arrays)
    jax.block_until_ready(m)
    jax.profiler.start_trace(log)
    for _ in range(3):
        state, m = compiled(state, arrays)
    jax.block_until_ready(m)
    jax.profiler.stop_trace()
    out["after_steps"] = dev.memory_stats()
    out["trace"] = trace.describe(log)
    red = trace.reduce(trace.load(log), 1.0)
    out["programs"] = red["programs"]
    out["busy_s"] = red["busy_s"]
    text = json.dumps(out, indent=1, default=str)
    if len(sys.argv) > 2:
        os.makedirs(os.path.dirname(sys.argv[2]), exist_ok=True)
        with open(sys.argv[2], "w") as f:
            f.write(text)
    print(text[-6000:])


if __name__ == "__main__":
    main()
