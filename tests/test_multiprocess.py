"""Real 2-process jax.distributed integration test.

Every multi-host code path — coordination bring-up, host-local batch
assembly, the collective checkpoint gather, chief-only writing,
barrier(), resume — previously ran only with a monkeypatched
process_count.  Here two actual processes (2 virtual CPU devices each,
4 global) train a (dp=4) mesh together through the public Trainer API;
the reference has no multi-worker test at all (SURVEY §4).
"""

import json
import os
import socket
import subprocess
import sys

import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "_multiproc_worker.py")
_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _worker_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    pypath = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join([_REPO] + pypath)
    env.pop("JAX_PLATFORM_NAME", None)
    return env


@pytest.mark.parametrize("mesh", ["4,1", "2,2", "2,2,bfloat16"])
@pytest.mark.slow
def test_two_process_distributed_train_checkpoint_resume(tmp_path, mesh):
    """mesh='4,1': pure dp, replicated params (easy checkpoint gather).
    mesh='2,2': params tp-shard ACROSS the two hosts, so the collective
    save must gather non-addressable shards — the hard path of
    checkpointer.state_to_arrays.  mesh='2,2,bfloat16': the same shape
    with the registry's bf16 gradient wire annotation (ISSUE 8) — the
    dp x tp composition the retired shard_map builder rejected, now
    running its bf16 dp all-reduce across two real processes."""
    port = _free_port()
    env = _worker_env()
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(port), str(pid), str(tmp_path),
             mesh],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("multi-process workers hung (collective desync?); "
                    "partial output:\n" + "\n---\n".join(
                        (p.communicate()[0] or "") for p in procs))
    for p, out in zip(procs, outs):
        assert p.returncode == 0, \
            f"worker rc={p.returncode}; output:\n{out[-4000:]}"

    infos = []
    for pid in (0, 1):
        with open(tmp_path / f"worker{pid}.json") as f:
            infos.append(json.load(f))

    # cluster shape seen from inside
    assert [i["process_index"] for i in infos] == [0, 1]
    assert all(i["process_count"] == 2 for i in infos)
    assert all(i["global_devices"] == 4 for i in infos)
    assert [i["is_chief"] for i in infos] == [True, False]

    # both hosts agree on training progress and the restored checkpoint
    assert all(i["final_step"] == 5 for i in infos), infos
    # latest checkpoint is the final step-5 save (not the step-3 cadence
    # save) — and both hosts restore the same one
    assert all(i["restored_step"] == 5 for i in infos), infos
    assert infos[0]["param_checksum"] == pytest.approx(
        infos[1]["param_checksum"], rel=0, abs=0), \
        "hosts restored different parameters from the shared checkpoint"
    assert all(i["resumed_step"] == 7 for i in infos), infos

    # chief-only writing: ONE events.jsonl record per step, even with
    # two processes sharing the train dir
    train_dir = tmp_path / "mp" / "train"
    with open(train_dir / "events.jsonl") as f:
        steps = [json.loads(line)["step"] for line in f if line.strip()]
    assert len(steps) == len(set(steps)), \
        f"duplicate per-step records — non-chief host wrote too: {steps}"
    # training ran steps 1..5 then resumed 6..7 (post-step numbering)
    assert set(steps) == set(range(1, 8)), steps

    # retention: checkpoints exist, written by the chief, readable
    ckpts = infos[0]["ckpt_files"]
    assert ckpts == infos[1]["ckpt_files"]
    assert "model.ckpt-3.npz" in ckpts and "model.ckpt-5.npz" in ckpts, \
        ckpts  # step-3 cadence save + final save, chief-written
