"""The serial tick's parts as per-layer metrics: the idle share of the
device while each child phase of the harvest and of the prefill is open,
the host time of an unpack, the request's prefill stage and the tick's
p95, each a metric file over a reader kind the harness already has, read
here over a hand-built capture and registry snapshot with known answers.
"""
import json
import os

import pytest

from harness import readers, splits, trace
from tests.tiny import BENCH

ROOT = os.path.dirname(BENCH)
MS = 1e6  # ns

#: child metric -> (its phase, the accepted metric of the phase it opens in)
HARVEST = {"idle_in_unpack_dispatch.steady": "decode/unpack/dispatch",
           "idle_in_unpack_fetch.steady": "decode/unpack/fetch",
           "idle_in_unpack_result.steady": "decode/unpack/result",
           "idle_in_harvest_resolve.steady": "serve/harvest/resolve"}
PREFILL = {"idle_in_prefill_batch.steady": "decode/prefill/batch",
           "idle_in_prefill_dispatch.steady": "decode/prefill/dispatch"}
REGISTRY = {"unpack_host_ms.steady": ("profile/phase_seconds",
                                      {"phase": "serve/harvest/unpack"},
                                      "mean"),
            "prefill_stage_p95_ms.steady": ("serve/request_stage_seconds",
                                            {"stage": "prefill"}, "p95"),
            "tick_p95_ms.steady": ("profile/wall_seconds",
                                   {"wall": "serve/tick"}, "p95")}
NEW = list(HARVEST) + list(PREFILL) + list(REGISTRY)
LAYER = "scheduler (serve/batcher.py, decode/arena.py)"


def ev(name, start_ms, dur_ms):
    return (name, start_ms * MS, dur_ms * MS)


def _metric(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


def capture(gap_in_unpack=False):
    """A tick of 200 ms: the chunk (0-100), a harvest of two requests
    whose unpack programs run 104-105 and 115-116, a prefill whose
    program runs 160-175, a pack (176-178) and the next chunk (180-200).
    Every child tiles its parent, unless `gap_in_unpack` leaves 1 ms of
    the first unpack (106-107) in none of its three parts."""
    ops = [ev("%while.1 = f32[8]{0} while(...)", 0, 100),
           ev("%gather.2 = f32[8]{0} gather(...)", 104, 1),
           ev("%gather.2 = f32[8]{0} gather(...)", 115, 1),
           ev("%fusion.3 = f32[8]{0} fusion(...)", 160, 15),
           ev("%dynamic-update-slice.4 = f32[8]{0} dus(...)", 176, 2),
           ev("%while.1 = f32[8]{0} while(...)", 180, 20)]
    mods = [ev("jit_step_slots_jit(1)", 0, 100),
            ev("jit_unpack_slot_jit(2)", 104, 1),
            ev("jit_unpack_slot_jit(2)", 115, 1),
            ev("jit_prefill_jit(3)", 160, 15),
            ev("jit_pack_slot_jit(4)", 176, 2),
            ev("jit_step_slots_jit(1)", 180, 20)]
    first_result = ev("decode/unpack/result", 107, 3) if gap_in_unpack \
        else ev("decode/unpack/result", 106, 4)
    dispatch = [ev("serve/tick", 0, 200),
                ev("serve/dispatch", 0, 100),
                ev("serve/dispatch/wait_mask", 1, 98),
                ev("serve/harvest", 100, 30),
                ev("serve/harvest/unpack", 100, 10),
                ev("decode/unpack/dispatch", 100, 2),
                ev("decode/unpack/fetch", 102, 4),
                first_result,
                ev("serve/harvest/resolve", 110, 2),
                ev("serve/harvest/unpack", 112, 10),
                ev("decode/unpack/dispatch", 112, 1),
                ev("decode/unpack/fetch", 113, 4),
                ev("decode/unpack/result", 117, 5),
                ev("serve/harvest/resolve", 122, 8),
                ev("serve/prefill", 130, 40),
                ev("decode/prefill/batch", 130, 15),
                ev("decode/prefill/dispatch", 145, 25),
                ev("serve/pack", 170, 10),
                ev("serve/dispatch", 180, 20)]
    planes = {"/device:TPU:0": {trace.OPS_LINE: ops,
                                trace.MODULES_LINE: mods}}
    return {"planes": planes, "threads": [dispatch]}


def _share(phase, cap):
    return splits.read({"kind": "trace_phase", "stat": "idle_pct",
                        "phase": phase}, {"capture": cap})


def test_the_nine_are_appended_entries_of_the_scheduler_layer():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW):] == NEW
    for e in bench["per_layer"][-len(NEW):]:
        assert e == {"name": e["name"], "unit": e["unit"], "better": "lower",
                     "source": "program_span", "layer": LAYER,
                     "moves": "summary_p95_ms",
                     "workloads": ["pg_serve_steady"]}
        m = _metric(e["name"])
        assert (m["name"], m["unit"], m["layer"], m["moves"]) == (
            e["name"], e["unit"], LAYER, "summary_p95_ms")
    for name, phase in {**HARVEST, **PREFILL}.items():
        assert _metric(name)["source"] == {
            "kind": "trace_phase", "stat": "idle_pct", "phase": phase}
    for name, (series, labels, stat) in REGISTRY.items():
        assert _metric(name)["source"] == {
            "kind": "registry", "name": series, "labels": labels,
            "stat": stat, "scale": 1000.0}


def test_the_children_read_the_hand_built_capture_and_tile_their_parents():
    ctx = {"capture": capture()}
    got = {n: readers.read(_metric(n), ctx) for n in list(HARVEST)
           + list(PREFILL)}
    # idle ms of the 200 ms window, each over 2 ms a percent
    assert got == {"idle_in_unpack_dispatch.steady": pytest.approx(1.5),
                   "idle_in_unpack_fetch.steady": pytest.approx(3.0),
                   "idle_in_unpack_result.steady": pytest.approx(4.5),
                   "idle_in_harvest_resolve.steady": pytest.approx(5.0),
                   "idle_in_prefill_batch.steady": pytest.approx(7.5),
                   "idle_in_prefill_dispatch.steady": pytest.approx(7.5)}
    harvest = readers.read(_metric("idle_in_harvest.steady"), ctx)
    prefill = readers.read(_metric("idle_in_prefill.steady"), ctx)
    assert harvest == pytest.approx(14.0)
    assert prefill == pytest.approx(15.0)
    # where the children tile the parent, their shares sum to its share
    assert sum(got[n] for n in HARVEST) == pytest.approx(harvest)
    assert sum(got[n] for n in PREFILL) == pytest.approx(prefill)
    unpack = _share("serve/harvest/unpack", ctx["capture"])
    assert sum(got[n] for n in list(HARVEST)[:3]) == pytest.approx(unpack)


@pytest.mark.parametrize("gap", [False, True])
def test_a_childs_share_never_exceeds_its_parents(gap):
    cap = capture(gap_in_unpack=gap)
    parent = {"serve/harvest": ["serve/harvest/unpack",
                                "serve/harvest/resolve"],
              "serve/harvest/unpack": ["decode/unpack/dispatch",
                                       "decode/unpack/fetch",
                                       "decode/unpack/result"],
              "serve/prefill": ["decode/prefill/batch",
                                "decode/prefill/dispatch"]}
    for p, children in parent.items():
        whole = _share(p, cap)
        shares = [_share(c, cap) for c in children]
        assert all(0.0 <= s <= whole + 1e-12 for s in shares), (p, shares)
        assert sum(shares) <= whole + 1e-12
    if gap:  # 1 ms of the first unpack in none of its parts: 0.5 points
        parts = sum(_share(c, cap) for c in parent["serve/harvest/unpack"])
        assert _share("serve/harvest/unpack", cap) - parts == \
            pytest.approx(0.5)


def _hist(buckets, counts, total, n):
    return {"count": n, "sum": total, "buckets": buckets,
            "counts": counts, "min": 0.0, "max": buckets[-1]}


def test_the_registry_three_read_the_window_of_a_snapshot():
    q = [1e-3 * 2.0 ** (i / 4) for i in range(8)]  # quarter-octave bounds
    before = {
        "profile/phase_seconds|phase=serve/harvest/unpack":
            _hist(q, [0, 2, 0, 0, 0, 0, 0, 0, 0], 0.0024, 2),
        "profile/wall_seconds|wall=serve/tick": _hist(
            q, [0] * 9, 0.0, 0)}
    after = {
        # ten more unpacks of 4.6 ms in all: 0.46 ms each
        "profile/phase_seconds|phase=serve/harvest/unpack":
            _hist(q, [0, 12, 0, 0, 0, 0, 0, 0, 0], 0.0024 + 0.0046, 12),
        # twenty prefill stages, all in the bucket (q[1], q[2]]
        "serve/request_stage_seconds|stage=prefill":
            _hist(q, [0, 0, 20, 0, 0, 0, 0, 0, 0], 0.025, 20),
        # 100 ticks: 90 in (q[5], q[6]], 10 in (q[6], q[7]]
        "profile/wall_seconds|wall=serve/tick":
            _hist(q, [0, 0, 0, 0, 0, 0, 90, 10, 0], 0.25, 100)}
    ctx = {"registry0": before, "registry1": after, "window_s": 45.0,
           "harness": {}, "trace": None}
    got = {n: readers.read(_metric(n), ctx) for n in REGISTRY}
    assert got["unpack_host_ms.steady"] == pytest.approx(0.46)
    # rank 19 of 20 in one bucket: 19/20 of the way across it
    assert got["prefill_stage_p95_ms.steady"] == pytest.approx(
        1e3 * (q[1] + 0.95 * (q[2] - q[1])))
    # rank 95 of 100: the fifth of the ten in the last bucket
    assert got["tick_p95_ms.steady"] == pytest.approx(
        1e3 * (q[6] + 0.5 * (q[7] - q[6])))


def test_a_program_without_the_parts_reads_nothing_and_never_raises():
    """A program whose tick has no child phases (the parent of this
    change) has an empty share for each: no annotation of that name is
    open, so 0.0; a series it never booked reads None."""
    cap = capture()
    bare = {"planes": cap["planes"],
            "threads": [[e for e in cap["threads"][0]
                         if e[0].count("/") == 1
                         or e[0] in ("serve/harvest/unpack",
                                     "serve/dispatch/wait_mask")]]}
    for n in list(HARVEST) + list(PREFILL):
        assert readers.read(_metric(n), {"capture": bare}) == 0.0
    ctx = {"registry0": {}, "registry1": {}, "window_s": 45.0,
           "harness": {}, "trace": None}
    for n in REGISTRY:
        assert readers.read(_metric(n), ctx) is None
    # an untraced run has no capture: the trace kinds read nothing
    for n in list(HARVEST) + list(PREFILL):
        assert readers.read(_metric(n), {}) is None
