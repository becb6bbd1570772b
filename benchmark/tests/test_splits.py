"""The split readers (harness/splits.py, and `scope_roofline` over them
in harness/readers.py) on a hand-built capture with known answers, the
sums that say a split closes, the loader on a capture recorded here, and
the proof that a further phase, scope or stage metric is a file and an
entry only."""
import json
import os
import re
import threading
import time

import pytest

from harness import readers, splits, trace
from tests.tiny import BENCH

ROOT = os.path.dirname(BENCH)
MS = 1e6  # ns
SCOPES = ["topk", "vocab_dist", "beam_select"]

HLO = """
HloModule jit_step_slots_paged_jit
%body (p: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(step_slots_paged_jit)/while/body/closed_call/vmap(vocab_dist)/add" stack_frame_id=3}
  %sort.2 = (f32[8]{0}, s32[8]{0}) sort(%fusion.1, %iota), dimensions={0}, metadata={op_name="jit(step_slots_paged_jit)/while/body/closed_call/vmap(topk)/top_k"}
  %sort.3 = s32[8]{0} sort(%x), metadata={op_name="jit(step_slots_paged_jit)/while/body/closed_call/vmap(beam_select)/jit(argsort)/sort"}
  ROOT %gather.4 = f32[8]{0} gather(%y), metadata={op_name="jit(step_slots_paged_jit)/while/body/closed_call/vmap(beam_select)/vmap(topk)/gather"}
}
ENTRY %main {
  %while.5 = f32[8]{0} while(%t), condition=%cond, body=%body, metadata={op_name="jit(step_slots_paged_jit)/while"}
  %copy.6 = f32[8]{0} copy(%while.5)
}
"""


def ev(name, start_ms, dur_ms):
    return (name, start_ms * MS, dur_ms * MS)


def capture():
    """One slot-step run of 100 ms (a loop of 90 ms holding four
    operations, then a copy), one run of another program, two device
    gaps inside phases and one in none; a second thread whose
    annotation covers everything and must not count."""
    ops = [ev("%while.5 = f32[8]{0} while(...)", 0, 90),
           ev("%fusion.1 = f32[8]{0} fusion(...)", 0, 10),
           ev("%sort.2 = (f32[8]{0}, s32[8]{0}) sort(...)", 10, 60),
           ev("%sort.3 = s32[8]{0} sort(...)", 70, 5),
           ev("%gather.4 = f32[8]{0} gather(...)", 75, 15),
           ev("%copy.6 = f32[8]{0} copy(...)", 90, 10),
           # idle 100-130, then another program, idle 150-160, and the
           # next slot-step run's first operation
           ev("%fusion.9 = f32[1]{0} fusion(...)", 130, 20),
           ev("%fusion.1 = f32[8]{0} fusion(...)", 160, 40)]
    mods = [ev("jit_step_slots_paged_jit(77)", 0, 100),
            ev("jit_prefill_jit(5)", 130, 20),
            ev("jit_step_slots_paged_jit(77)", 160, 40)]
    dispatch = [ev("serve/tick", 0, 200),
                ev("serve/dispatch", 0, 102),
                ev("decode/slot_chunk", 0, 101),
                ev("serve/dispatch/wait_mask", 1, 100),
                ev("serve/harvest", 102, 18),  # idle 102-120 in it
                ev("serve/harvest/unpack", 103, 8),
                ev("serve/harvest/unpack", 111, 8),
                ev("serve/prefill", 125, 30),  # idle 125-130, 150-155
                ev("serve/dispatch", 158, 42)]  # idle 158-160
    other = [ev("serve/harvest", 0, 200)]
    planes = {"/device:TPU:0": {trace.OPS_LINE: ops,
                                trace.MODULES_LINE: mods}}
    return {"planes": planes, "threads": [other, dispatch]}


PHASES = ["serve/prefill", "serve/pack", "serve/dispatch", "serve/harvest"]


def test_idle_splits_by_the_dispatch_threads_phases_and_sums():
    r = splits.idle_by_phase(capture(), PHASES)
    assert r["window_s"] == pytest.approx(0.200)
    assert r["idle_s"] == pytest.approx(0.040)  # 100-130 and 150-160
    # 100-102 and 158-160 in dispatch; 102-120 in harvest; 125-130 and
    # 150-155 in prefill; 120-125 and 155-158 in none of the four
    assert r["serve/dispatch"] == pytest.approx(0.004)
    assert r["serve/harvest"] == pytest.approx(0.018)
    assert r["serve/prefill"] == pytest.approx(0.010)
    assert r["serve/pack"] == 0.0
    assert r[None] == pytest.approx(0.008)
    assert sum(r[p] for p in PHASES) + r[None] == pytest.approx(r["idle_s"])
    # the other thread's all-covering serve/harvest was ignored
    assert splits.dispatch_thread(capture()["threads"])[0][0] == "serve/tick"


def _phase_spec(phase):
    if phase is None:
        return {"kind": "trace_phase", "stat": "idle_pct", "phase": None,
                "none_of": PHASES}
    return {"kind": "trace_phase", "stat": "idle_pct", "phase": phase}


def test_the_five_idle_shares_sum_to_the_devices_idle_share():
    ctx = {"capture": capture()}
    shares = [splits.read(_phase_spec(p), ctx) for p in PHASES + [None]]
    assert shares == [pytest.approx(5.0), 0.0, pytest.approx(2.0),
                      pytest.approx(9.0), pytest.approx(4.0)]
    r = trace.reduce(ctx["capture"]["planes"], window_s=0.200)
    idle = readers.read({"source": {"kind": "trace_device"}},
                        {"trace": r, "peaks": None})
    assert sum(shares) == pytest.approx(idle)


def test_self_time_takes_the_nested_operations_out():
    ops = capture()["planes"]["/device:TPU:0"][trace.OPS_LINE]
    got = {splits.instruction(n): ns / MS
           for n, _, ns in splits.self_times(ops)[:6]}
    assert got == {"%while.5": pytest.approx(0.0),  # 90 - (10+60+5+15)
                   "%fusion.1": pytest.approx(10.0),
                   "%sort.2": pytest.approx(60.0),
                   "%sort.3": pytest.approx(5.0),
                   "%gather.4": pytest.approx(15.0),
                   "%copy.6": pytest.approx(10.0)}


def test_scope_map_reads_instruction_names_and_splits_transforms():
    m = splits.scope_map(HLO)
    assert m["%sort.2"][-2:] == ["topk", "top_k"]
    assert "vmap" in m["%fusion.1"] and "vocab_dist" in m["%fusion.1"]
    assert m["%gather.4"][-3:] == ["vmap", "topk", "gather"]  # ROOT form
    assert "%copy.6" not in m  # no metadata: claimed by no scope


def _scope_spec(scope):
    return {"kind": "trace_scope", "stat": "ms_per_call",
            "program": "^jit_step_slots(_paged)?_jit$", "scope": scope,
            "scopes": SCOPES}


def test_scope_shares_partition_the_programs_device_time():
    ctx = {"capture": capture(), "slot_step_hlo": HLO}
    by = {s: splits.read(_scope_spec(s), ctx) for s in SCOPES + [None]}
    # two runs of the program; the other program's fusion.9 is left out
    assert by["topk"] == pytest.approx((60 + 15) / 2)  # innermost claims
    assert by["vocab_dist"] == pytest.approx((10 + 40) / 2)
    assert by["beam_select"] == pytest.approx(5 / 2)
    assert by[None] == pytest.approx(10 / 2)  # the copy; while's self 0
    r = trace.reduce(ctx["capture"]["planes"], window_s=0.200)
    chunk = readers.read(
        {"source": {"kind": "trace_program", "stat": "mean_ms",
                    "pattern": "^jit_step_slots(_paged)?_jit$"}},
        {"trace": r, "peaks": None})
    assert sum(by.values()) == pytest.approx(chunk)


def test_nothing_to_read_is_none_and_never_raises():
    cap = capture()
    spec = _scope_spec("topk")
    assert splits.read(spec, {}) is None  # an untraced run
    assert splits.read(spec, {"capture": cap}) is None  # no text: parent
    # a program compiled without the scopes: no share of it exists, the
    # unscoped one included
    bare = re.sub(r"vmap\((\w+)\)/", "", HLO)
    for s in SCOPES + [None]:
        assert splits.read(_scope_spec(s), {"capture": cap,
                                            "slot_step_hlo": bare}) is None
    # no such program in the capture
    other = dict(spec, program="^jit_train_step$")
    assert splits.read(other, {"capture": cap, "slot_step_hlo": HLO}) is None
    # a program without the annotations (the parent), or no TPU plane
    no_phases = {"planes": cap["planes"], "threads": []}
    assert splits.read(_phase_spec("serve/harvest"),
                       {"capture": no_phases}) is None
    assert splits.read(_phase_spec(None), {"capture": no_phases}) is None
    assert splits.read(_phase_spec("serve/harvest"),
                       {"capture": {"planes": {}, "threads":
                                    cap["threads"]}}) is None
    with pytest.raises(KeyError):
        splits.read({"kind": "trace_other"}, {"capture": cap})


def test_loader_keeps_phase_named_events_per_thread(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()

    def elsewhere():
        with jax.profiler.TraceAnnotation("serve/harvest"):
            time.sleep(0.001)

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.SYNC_EVENT):
        pass
    for slot in range(3):
        with jax.profiler.TraceAnnotation("serve/dispatch", fill=slot):
            with jax.profiler.TraceAnnotation("serve/dispatch/wait_mask"):
                f(x).block_until_ready()
    t = threading.Thread(target=elsewhere)
    t.start()
    t.join()
    jax.profiler.stop_trace()
    cap = splits.load(str(tmp_path))
    assert sorted(len(t) for t in cap["threads"]) == [1, 6]
    names = [n for n, _, _ in splits.dispatch_thread(cap["threads"])]
    assert names.count("serve/dispatch") == 3  # attrs are not in the name
    assert names.count("serve/dispatch/wait_mask") == 3
    # the sync annotation and the runtime's own events are not phases
    assert trace.SYNC_EVENT in cap["planes"].get("host", {})
    # a CPU capture has no TPU plane: nothing to read, nothing raised
    assert splits.read(_phase_spec("serve/dispatch"), {"capture": cap}) is None


def _split_metrics():
    """{name: (BENCHMARK.json entry, metric file)} of the per-layer
    metrics whose reader is one of the split kinds."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = {}
    for e in bench["per_layer"]:
        with open(os.path.join(BENCH, "metrics", e["name"] + ".json")) as f:
            m = json.load(f)
        if m["source"]["kind"] in ("trace_phase", "trace_scope",
                                   "scope_roofline"):
            out[e["name"]] = (e, m)
    return out


def test_the_split_metrics_are_whole_and_read_the_hand_built_capture():
    both = _split_metrics()
    assert len(both) == 13
    files = {n: m for n, (_, m) in both.items()}
    # each partition is whole: one metric per member and the remainder
    scope = [m["source"] for m in files.values()
             if m["source"]["kind"] == "trace_scope"]
    assert all(s["scopes"] == scope[0]["scopes"] for s in scope)
    assert sorted(str(s["scope"]) for s in scope) == sorted(
        scope[0]["scopes"] + ["None"])
    phase = [m["source"] for m in files.values()
             if m["source"]["kind"] == "trace_phase"]
    rest = [s for s in phase if s["phase"] is None]
    assert len(rest) == 1 and sorted(rest[0]["none_of"]) == sorted(
        s["phase"] for s in phase if s["phase"] is not None)
    # a scope's roofline is over the same self time as its device time
    roof = files["topk_roofline.steady"]["source"]
    same = files["topk_device_ms.steady"]["source"]
    assert [roof[k] for k in ("program", "scope", "scopes")] == [
        same[k] for k in ("program", "scope", "scopes")]
    # and they read the hand-built capture without a line of code more
    ctx = {"capture": capture(), "slot_step_hlo": HLO}
    got = {n: readers.read(m, ctx) for n, m in files.items()
           if m["source"]["kind"] != "scope_roofline"}
    assert got["idle_in_harvest.steady"] == pytest.approx(9.0)
    assert got["topk_device_ms.steady"] == pytest.approx(37.5)
    assert got["lstm_cell_device_ms.steady"] == 0.0


def _roofline_ctx(occupancy=True):
    """The hand-built capture with what a count needs beside it: two
    slots of four occupied, beam 2, rows of 8 + 2 floats, 5 steps a run."""
    occ = {"count": 10, "sum": 5.0, "buckets": [1.0], "counts": [10, 0],
           "min": 0.5, "max": 0.5}
    return {"capture": capture(), "slot_step_hlo": HLO,
            "family": "pointer_generator",
            "hparams": {"beam_size": 2, "vocab_size": 8,
                        "max_oov_buckets": 2},
            "deployment": {"slots": 4, "chunk": 5},
            "harness": {"mean_article_len": 10.0},
            "registry0": {},
            "registry1": {"serve/slot_occupancy": occ} if occupancy else {},
            "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e6}}


def test_scope_roofline_is_the_least_time_over_the_scopes_self_time():
    spec = {"source": {"kind": "scope_roofline", "count": "topk_rows",
                       "program": "^jit_step_slots(_paged)?_jit$",
                       "scope": "topk", "scopes": SCOPES}}
    # 2 occupied x beam 2 x 10 floats x 4 B x 5 steps = 800 B a run:
    # 0.8 ms at 1 MB/s, over topk's 37.5 ms a run
    assert readers.read(spec, _roofline_ctx()) == pytest.approx(
        0.8 / 37.5 * 100.0)
    # nothing to read is None, never 0: no occupancy in the window, no
    # capture, a scope the program does not carry, a scope with no time
    assert readers.read(spec, _roofline_ctx(occupancy=False)) is None
    assert readers.read(spec, dict(_roofline_ctx(), capture=None)) is None
    other = {"source": dict(spec["source"], scope="flash", scopes=None)}
    assert readers.read(other, _roofline_ctx()) is None
    empty = {"source": dict(spec["source"], scope="lstm_cell",
                            scopes=SCOPES + ["lstm_cell"])}
    assert readers.read(empty, _roofline_ctx()) is None
    # a count nobody has is an error that names it, not a silent None
    bad = {"source": dict(spec["source"], count="no_such_rows")}
    with pytest.raises(ValueError, match="no_such_rows"):
        readers.read(bad, _roofline_ctx())


def test_a_further_phase_scope_or_stage_metric_is_a_file_only():
    """A child phase, another program's scope and another stage, each
    read by a spec nobody wrote code for."""
    ctx = {"capture": capture(), "slot_step_hlo": HLO}
    wait = {"kind": "trace_phase", "stat": "idle_pct",
            "phase": "serve/dispatch/wait_mask"}
    assert splits.read(wait, ctx) == pytest.approx(0.5)  # 100-101 of 200
    sort_only = {"kind": "trace_scope", "stat": "ms_per_call",
                 "program": "^jit_step_slots_paged_jit$", "scope": "argsort"}
    assert splits.read(sort_only, ctx) == pytest.approx(2.5)
    # a stage metric is the accepted `registry` kind over the labelled
    # histogram: the three that BENCHMARK.json gained are such files
    for name, stage in (("slot_wait_p95_ms.steady", "slot_wait"),
                        ("resident_p95_ms.steady", "resident"),
                        ("harvest_wait_p95_ms.steady", "harvest")):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["source"] == {
            "kind": "registry", "name": "serve/request_stage_seconds",
            "labels": {"stage": stage}, "stat": "p95", "scale": 1000.0}
    hist = {"count": 4, "sum": 1.0, "buckets": [0.1, 0.2, 0.4],
            "counts": [0, 4, 0, 0], "min": 0.11, "max": 0.19}
    queue = {"name": "queue_stage_p50_ms.steady", "source": {
        "kind": "registry", "name": "serve/request_stage_seconds",
        "labels": {"stage": "queue"}, "stat": "p50", "scale": 1000.0}}
    got = readers.read(queue, {
        "registry0": {}, "registry1": {
            "serve/request_stage_seconds|stage=queue": hist},
        "window_s": 1.0, "harness": {}, "trace": None})
    assert got == pytest.approx(150.0)
    # a program without the stage clock (the parent): nothing, no error
    assert readers.read(queue, {"registry0": {}, "registry1": {},
                                "window_s": 1.0, "harness": {},
                                "trace": None}) is None
