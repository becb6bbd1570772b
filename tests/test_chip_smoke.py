"""chip_smoke.py's phases at a tiny size on the CPU, its refusal to run
without a TPU, and the behaviours this bring-up pinned: `auto` is the
chunked beam loop on every backend, a forced flash kernel off-TPU is an
error, an unknown device has no peak, one process per chip."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


chip_smoke = _load("chip_smoke")

from textsummarization_on_flink_tpu import utils  # noqa: E402
from textsummarization_on_flink_tpu.config import HParams  # noqa: E402
from textsummarization_on_flink_tpu.decode import beam_search  # noqa: E402
from textsummarization_on_flink_tpu.models import transformer as tfm  # noqa: E402
from textsummarization_on_flink_tpu.resilience.errors import (  # noqa: E402
    DeviceOwnershipError,
)
from textsummarization_on_flink_tpu.serve import procfleet  # noqa: E402
from textsummarization_on_flink_tpu.train import trainer as trainer_lib  # noqa: E402


def _tiny(work, **kw) -> HParams:
    base = dict(hidden_dim=16, emb_dim=8, vocab_size=200, batch_size=4,
                max_enc_steps=32, max_dec_steps=8, beam_size=2,
                min_dec_steps=1, max_oov_buckets=8, num_heads=4,
                enc_layers=2, dec_layers=2, log_root=str(work),
                seed=chip_smoke.SEED)
    base.update(kw)
    return HParams(**base)


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """The tiny train phase's outputs, shared by the phases that decode
    and serve its checkpoint (as in the script's default run)."""
    work = tmp_path_factory.mktemp("chip_smoke")
    hps = _tiny(work)
    vocab = chip_smoke.make_vocab(hps)
    train_glob, decode_glob = chip_smoke.make_data(str(work), hps)
    checked = chip_smoke.phase_train(hps, vocab, train_glob)
    return hps, vocab, train_glob, decode_glob, checked


def test_phase_train(trained):
    checked = trained[4]
    assert len(checked["spd_default"]["losses"]) == 8
    assert checked["spd4"]["steps_per_dispatch"] == 4
    assert len(checked["unchanged_param_leaves"]) <= 1
    assert checked["restore_equals_saved"]


def test_phase_decode(trained, monkeypatch):
    hps, vocab, _, decode_glob, _ = trained
    monkeypatch.delenv("TS_BEAM_LOOP", raising=False)
    checked = chip_smoke.phase_decode(hps, vocab, decode_glob)
    assert checked["summaries"] == hps.batch_size
    assert checked["loop_auto"] == "chunked"
    for kind in ("scan", "while"):
        eq = checked["loop_equality"][kind]
        assert eq["token_equal"] == eq["rows"] == hps.batch_size


def test_phase_serve(trained):
    hps, vocab = trained[0], trained[1]
    meter = chip_smoke.CompileMeter().install()
    checked = chip_smoke.phase_serve(
        hps.replace(serve_slots=4, serve_buckets="8,16,32"), vocab, meter)
    for engine in ("microbatch", "continuous", "continuous_paged"):
        assert checked[engine]["requests"] == chip_smoke.SERVE_REQUESTS
        assert checked[engine]["compiles_after_warmup"] == 0
    for engine in ("continuous", "continuous_paged"):
        arena = checked[engine]
        assert arena["arena_pages_free_at_end"] == arena["arena_pages"]
        eq = checked["engine_equality"][engine]
        assert eq["token_equal"] == eq["rows"]
    # no option: every slot at full length, nothing waits; two articles'
    # worth of pages: admissions wait, and the tokens are the same
    assert checked["continuous"]["arena_pages"] == 4 * (
        checked["continuous_paged"]["arena_pages"] // 2)
    assert checked["continuous"]["arena_alloc_failures"] == 0
    eq = checked["arena_equality"]
    assert eq["token_equal"] == eq["rows"] == chip_smoke.SERVE_REQUESTS


def test_phase_transformer(trained):
    hps, vocab, train_glob, decode_glob, _ = trained
    checked = chip_smoke.phase_transformer(hps, vocab, train_glob,
                                           decode_glob)
    assert len(checked["losses"]) == 4
    assert checked["summaries"] == hps.batch_size


def test_phase_multichip_on_virtual_devices(tmp_path, monkeypatch):
    """The four-chip phase on conftest's virtual CPU devices."""
    # what the one-device step is traced under, read on the thread that
    # dispatches it: the CPU ignores matmul precision, so only this
    # shows that the yardstick run (and no other) is a `highest` one
    traced_under = []
    build = trainer_lib.Trainer._build_step_fn

    def spy(self):
        step = build(self)

        def dispatched(*args):
            traced_under.append(jax.config.jax_default_matmul_precision)
            return step(*args)

        return dispatched

    monkeypatch.setattr(trainer_lib.Trainer, "_build_step_fn", spy)
    hps = _tiny(tmp_path, batch_size=8)
    vocab = chip_smoke.make_vocab(hps)
    train_glob, decode_glob = chip_smoke.make_data(str(tmp_path), hps)
    checked = chip_smoke.phase_multichip(hps, vocab, train_glob,
                                         decode_glob)
    assert len(checked["train_shard_devices"]) == 4
    assert len(checked["decode_mesh_devices"]) == 4
    assert checked["max_relative_loss_diff"] <= 1e-4  # f32 on the CPU
    assert traced_under == [None] * 4 + ["highest"] * 4
    worst = checked["param_drift_worst"][0]
    assert set(worst) == {"leaf", "diff", "scale", "default_vs_highest",
                          "allowed", "of_allowed"}
    assert 0 < worst["of_allowed"] <= 1.0  # two programs ran, and agree
    eq = checked["decode_equality"]
    assert eq["token_equal"] == eq["rows"] == hps.batch_size
    specs = {p["spec"] for p in checked["param_shardings"].values()}
    assert any("tp" in s for s in specs), specs


def test_param_drift_is_held_to_the_default_vs_highest_yardstick():
    bias = chip_smoke.MULTICHIP_CANCELLING_LEAVES[0][2:-2].split("']['")
    assert bias == ["decoder", "attention", "linear_bias"]

    def tree(w, v, b):
        return {"decoder": {"attention": {"linear_bias": jnp.full((3,), b)}},
                "v": jnp.full((2,), v), "w": jnp.full((2, 2), w)}

    single = tree(1.0, 1.0, 1e-7)
    highest = tree(1.0 + 1e-3, 1.0, 1e-7)      # w's yardstick: 1e-3
    drift = {d["leaf"]: d for d in chip_smoke._param_drift(
        single, tree(1.0 + 5e-4, 1.0 + 5e-7, 1.05e-7), highest)}
    assert drift["['w']"]["of_allowed"] == pytest.approx(0.5, rel=1e-3)
    # no yardstick: the f32 floor, relative for v, absolute for the bias
    assert drift["['v']"]["of_allowed"] == pytest.approx(0.5, rel=1e-1)
    assert drift["['decoder']['attention']['linear_bias']"][
        "of_allowed"] == pytest.approx(0.5, rel=1e-1)
    worst = chip_smoke._param_drift(
        single, tree(1.0 + 2e-3, 1.0, 1e-7), highest)[0]
    assert worst["leaf"] == "['w']" and worst["of_allowed"] > 1.0


@pytest.mark.parametrize("kernel_err,formula_err,passes", [
    (4.0e-3, 4.4e-3, True),    # the v5e's T400/hd32 reading
    (8.0e-3, 4.4e-3, False),   # the same kernel twice as far off
    (1.7e-3, 1.8e-3, True),    # T2048/hd128
    (3.5e-3, 1.8e-3, False),
    (1.9e-3, 0.0, True),       # a backend whose default is exact: floor
    (1.2e-2, 9.0e-3, False),   # the cap holds whatever the formula loses
])
def test_flash_tolerance_is_like_for_like(kernel_err, formula_err, passes):
    tol = chip_smoke.flash_tolerance(formula_err, scale=1.0)
    assert (kernel_err <= tol) == passes


@pytest.mark.parametrize("kernel,args", [
    ("kernel_fused_attention", (2, 128, 128, 1e-5)),
    ("kernel_flash", (2, 128, 256, 2)),
])
def test_kernels_cannot_pass_off_the_chip(kernel, args):
    """Compiled, never interpreted and never the formula under the
    kernel's name: off the chip the kernels phase can only fail."""
    with pytest.raises(ValueError, match="interpret mode|needs a TPU"):
        getattr(chip_smoke, kernel)(*args)


def test_compare_hyps_tie_rule():
    base = {0: ([1, 2, 3], -1.0), 1: ([4, 5], -2.0)}
    same = chip_smoke.compare_hyps("a", base, "b", dict(base))
    assert same == {"rows": 2, "token_equal": 2, "near_ties": []}
    near = chip_smoke.compare_hyps(
        "a", base, "b", {0: ([1, 9, 3], -1.00005), 1: ([4, 5], -2.0)})
    assert near["token_equal"] == 1
    assert near["near_ties"][0]["first_differing_step"] == 1
    with pytest.raises(chip_smoke.SmokeFailure, match="beyond a near-tie"):
        chip_smoke.compare_hyps(
            "a", base, "b", {0: ([1, 9, 3], -1.5), 1: ([4, 5], -2.0)})


@pytest.mark.parametrize("argv", [[], ["--multichip"]])
def test_main_refuses_a_cpu_backend(argv, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out and out.strip() == ""


def test_success_line_key_set():
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    line = json.loads(chip_smoke.success_line([Dev()]))
    assert line == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_compile_cache_helper_env_form():
    env = {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}
    assert utils.set_default_compile_cache(env) == "/somewhere/else"
    assert env == {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}
    env = {}
    want = os.path.join(_REPO, ".jax_cache")
    assert utils.set_default_compile_cache(env) == want
    assert env == {"JAX_COMPILATION_CACHE_DIR": want}


def test_compile_cache_helper_leaves_a_set_directory_alone(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/the/machine")
    before = jax.config.jax_compilation_cache_dir
    assert utils.set_default_compile_cache() == "/set/by/the/machine"
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("platforms", ["", "cpu", "tpu", "proxy,cpu"])
def test_loop_kind_auto_is_chunked_whatever_the_environment(
        monkeypatch, platforms):
    monkeypatch.delenv("TS_BEAM_LOOP", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.setattr(jax, "default_backend", lambda: 1 / 0)
    assert beam_search._loop_kind() == "chunked"
    assert beam_search._loop_kind("auto") == "chunked"
    assert beam_search._loop_kind("scan") == "scan"


def test_flash_forced_on_raises_off_tpu(monkeypatch):
    monkeypatch.setenv("TS_FLASH", "on")
    hps = HParams(model_family="transformer", hidden_dim=1024, num_heads=8)
    with pytest.raises(ValueError, match="needs a TPU backend"):
        tfm._use_flash(hps, 2048)
    monkeypatch.setenv("TS_FLASH", "auto")
    assert tfm._use_flash(hps, 2048) is False


def test_peak_flops_unknown_device_kind_raises():
    bench = _load("bench")

    class Dev:
        device_kind = "Banana9000"

    with pytest.raises(ValueError, match="banana9000"):
        bench.peak_flops_for(Dev())


@pytest.mark.parametrize("held,chips,replicas,platforms,refused", [
    (True, 1, 1, "", True),      # the parent holds the chip
    (False, 1, 2, "", True),     # two real children, nobody pinned
    (False, 4, 4, "tpu", True),  # four chips do not help: no pinning
    (False, 1, 1, "", False),    # one child, parent off the device
    (True, 1, 2, "cpu", False),  # CPU children never touch the chip
    (False, 0, 3, "", False),    # no TPU on this host
])
def test_procfleet_one_process_per_chip(monkeypatch, tmp_path, held, chips,
                                        replicas, platforms, refused):
    monkeypatch.setattr(procfleet, "_tpu_claims", lambda: (held, chips))
    reason = procfleet._chip_conflict(replicas, platforms)
    assert (reason is not None) == refused
    if not refused:
        return
    env = dict(os.environ, JAX_PLATFORMS=platforms)
    fleet = procfleet.ProcFleet(HParams(serve_replicas=replicas),
                                state_dir=str(tmp_path), child_env=env)
    with pytest.raises(DeviceOwnershipError, match="refused to start"):
        fleet.start()
    assert all(p.proc is None for p in fleet.procs)  # nothing spawned
