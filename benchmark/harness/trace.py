"""From a profiler trace to numbers: device busy time, idle gaps, the time
of each jitted program, the device operations that took most time.

`load(dir)` reads the newest `.xplane.pb` under a `jax.profiler` log
directory with `jax.profiler.ProfileData` into plain lists;
`reduce(planes, ...)` is pure Python over those lists and is what the
tests check on a hand-built trace.

A TPU's plane is named `/device:TPU:<n>`.  Its line `XLA Ops` holds one
event per executed HLO operation and `XLA Modules` one event per run of a
compiled program, named `<jit name>(<fingerprint>)`.  Busy time is the
union of the `XLA Ops` intervals (nested events such as a loop and its
body are counted once); where a plane has no such line the union of the
module runs stands in.  Times in the file are nanoseconds on the
profiler's clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, duration_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC_EVENT = "bench_sync"


def newest_xplane(log_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def parse(log_dir: str):
    """The newest capture under `log_dir` as `jax.profiler.ProfileData`."""
    from jax.profiler import ProfileData

    path = newest_xplane(log_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return ProfileData.from_file(path)


def load(log_dir: str, data=None) -> Dict[str, Dict[str, List[Event]]]:
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}}
    for the device planes, plus under the key "host" the one line that
    holds the harness's own sync annotation (if any).  `data`: the
    capture where the caller has parsed it already."""
    data = data if data is not None else parse(log_dir)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        is_dev = DEVICE_PLANE.match(plane.name) is not None
        if not is_dev and not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            if is_dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events
                   if is_dev or e.name == SYNC_EVENT]
            if not evs:
                continue
            if is_dev:
                out.setdefault(plane.name, {})[line.name] = evs
            else:
                out.setdefault("host", {})[SYNC_EVENT] = evs
    return out


def describe(log_dir: str, limit: int = 6) -> Dict[str, Any]:
    """Every plane and line of the newest trace with its event count and
    first few event names: what a builder reads before trusting `load`."""
    data = parse(log_dir)
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {"events": len(evs),
                                "first": [e.name for e in evs[:limit]]}
        out[plane.name] = lines
    return out


def union_seconds(events: Sequence[Event]) -> Tuple[float, List[Tuple[float, float]]]:
    """(seconds covered by the union of the intervals, the merged
    intervals as (start_ns, end_ns))."""
    merged: List[List[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return (sum(e - s for s, e in merged) / 1e9,
            [(s, e) for s, e in merged])


def program_name(event_name: str) -> str:
    """`jit_train_step(123...)` -> `jit_train_step`."""
    return event_name.split("(", 1)[0]


def reduce(planes: Dict[str, Dict[str, List[Event]]], window_s: float,
           chips: int = 1,
           phases: Optional[Sequence[Tuple[float, float, str]]] = None,
           sync_epoch_ns: Optional[float] = None) -> Dict[str, Any]:
    """The traced window in numbers.

    window_s: the length of the traced window on the host's clock.
    phases: the program's own host phases as (start_epoch_ns,
    end_epoch_ns, name); with sync_epoch_ns (the epoch time at which the
    harness's sync annotation was emitted) they name the idle gaps.

    Returns busy_s (averaged over the device planes found, at most
    `chips`), programs {name: {"calls", "total_s", "mean_ms"}} summed
    over devices and divided by their number, device_ops (top 10 by
    time) and idle_gaps (the 10 longest, named by the host phase that
    overlaps them most, else "host")."""
    devs = sorted(k for k in planes if DEVICE_PLANE.match(k))[:chips]
    if not devs:
        return {"busy_s": 0.0, "window_s": window_s, "programs": {},
                "device_ops": [], "idle_gaps": [], "devices": 0}
    busy, programs, ops = [], {}, {}
    gaps: List[Tuple[float, float]] = []
    for d in devs:
        lines = planes[d]
        base = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        b, merged = union_seconds(base)
        busy.append(b)
        if d == devs[0]:
            gaps = [(merged[i][1], merged[i + 1][0])
                    for i in range(len(merged) - 1)]
        for name, _, dur in lines.get(MODULES_LINE, []):
            p = programs.setdefault(program_name(name), [0, 0.0])
            p[0] += 1
            p[1] += dur / 1e9
        for name, _, dur in lines.get(OPS_LINE, []):
            ops[name] = ops.get(name, 0.0) + dur / 1e9
    n = len(devs)
    prog_out = {k: {"calls": c / n, "total_s": t / n,
                    "mean_ms": 1e3 * t / c if c else 0.0}
                for k, (c, t) in programs.items()}
    # nested events (a while loop and its body) would double-count in a
    # plain sum; report the top operations as they are named, for reading
    top_ops = sorted(((k, v / n) for k, v in ops.items()),
                     key=lambda kv: -kv[1])[:10]
    offset = None
    sync = planes.get("host", {}).get(SYNC_EVENT)
    if sync and sync_epoch_ns is not None:
        offset = sync_epoch_ns - sync[0][1]  # epoch = profiler + offset
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        label = "host"
        if offset is not None and phases:
            best = 0.0
            for ps, pe, name in phases:
                ov = min(e + offset, pe) - max(s + offset, ps)
                if ov > best:
                    best, label = ov, name
        named.append([label, (e - s) / 1e9])
    return {"busy_s": sum(busy) / n, "window_s": window_s,
            "programs": prog_out, "device_ops": [list(x) for x in top_ops],
            "idle_gaps": named, "devices": n}


def match_programs(programs: Dict[str, Dict[str, float]],
                   pattern: str) -> Dict[str, float]:
    """Sum the programs whose name matches the regular expression."""
    rx = re.compile(pattern)
    calls = total = 0.0
    for name, p in programs.items():
        if rx.search(name):
            calls += p["calls"]
            total += p["total_s"]
    return {"calls": calls, "total_s": total,
            "mean_ms": 1e3 * total / calls if calls else 0.0}
