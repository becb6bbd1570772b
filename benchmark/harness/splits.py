"""The reader kinds that split what the other metrics only total, both
over ONE profiler capture and on its own clock: the program's host phases
(`Profiler.phase` opens a `jax.profiler.TraceAnnotation`, so they lie in
the capture's host plane, on the thread that ran them) and the device
operations' named scopes (`jax.named_scope` in the decode programs).

  trace_phase  idle_pct: 100 x the seconds of the captured window in
               which no `XLA Ops` event runs on the device AND an
               annotation named "phase" is open on the dispatch thread
               (or, with "none_of", none of the listed ones is), over
               the window.  Top-level phases of one thread never overlap,
               so such shares and the one "none_of" share add up to the
               device's idle share.
  trace_scope  ms_per_call: SELF time (an operation's duration less that
               of the operations nested in it: a loop and its body are
               both events) of the `XLA Ops` events inside runs of the
               programs matching "program", claimed by "scope", over
               those runs.  An operation's scopes are the parts of the
               `op_name` of the instruction with the event's name in the
               compiled program's text; where several of "scopes" (the
               whole partition, default just "scope") are in the path the
               innermost claims it, and `"scope": null` reads what none
               of them claims — so the metrics of one partition add up
               to the program's device time.
  scope_roofline  (readers.py) the same self time under the least time
               for a named count: `scope_reading` is what it shares.

`load(log_dir)` reads a capture into plain lists; `idle_by_phase`,
`self_times` and `read` are pure Python over them and are what the tests
check on a hand-built capture.  A reader that finds nothing to read (a
program without the annotations or the scopes, a capture without a TPU
plane) returns None and never raises.

The chip's trace does not carry `op_name` on its events (looked at with
`trace.describe` and the events' stats, PERF.md section 6, PR 25): an
event is named by the instruction's text, `%sort.57 = ...`.  The map
therefore comes from `ServingServer.compiled_slot_step().as_text()`.
JAX's persistent compile cache leaves metadata out of its key unless
`jax_compilation_cache_include_metadata_in_key` is set: an executable
loaded from a cache that an older build wrote carries THAT build's
op_names.  Whoever takes the text sets the flag before the first compile.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from harness import trace as trace_lib

Event = Tuple[str, float, float]  # (name, start_ns, duration_ns)
Span = Tuple[float, float]  # (start_ns, end_ns)

#: a host-plane event that can be a phase of the program: a lower-case
#: path such as `serve/harvest/unpack` (the runtime's own events are
#: `Class::Method`, `$file.py:1 fn` or single words)
PHASE_NAME = re.compile(r"^[a-z_0-9]+(/[a-z_0-9]+)+$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def load(log_dir: str) -> Dict[str, Any]:
    """{"planes": what `trace.load` gives (device lines),
    "threads": one list of phase-named events per host line}."""
    data = trace_lib.parse(log_dir)  # one parse of the file for both
    return {"planes": trace_lib.load(log_dir, data),
            "threads": host_threads(data)}


def host_threads(data) -> List[List[Event]]:
    """The phase-named events of a parsed capture, one list per host
    line (a line is a thread)."""
    threads: List[List[Event]] = []
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events if PHASE_NAME.match(e.name)]
            if evs:
                threads.append(evs)
    return threads


def dispatch_thread(threads: Sequence[Sequence[Event]]) -> List[Event]:
    """The thread that opened the most phases: the serving loop's
    dispatch thread, the trainer's loop.  Annotations of other threads
    are left out of every attribution."""
    return list(max(threads, key=len)) if threads else []


def _merge(spans: Sequence[Span]) -> List[Span]:
    out: List[List[float]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(a: Sequence[Span], b: Sequence[Span]) -> float:
    """Nanoseconds in both of two merged, sorted span lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _first_device(planes) -> Optional[Dict[str, List[Event]]]:
    devs = sorted(k for k in planes if trace_lib.DEVICE_PLANE.match(k))
    return planes[devs[0]] if devs else None


def idle_by_phase(capture: Dict[str, Any],
                  names: Sequence[str]) -> Optional[Dict[Any, float]]:
    """{name: idle seconds while an annotation of that name was open on
    the dispatch thread, None: idle seconds while none of `names` was,
    "window_s", "idle_s"} — or None without a device line or a phase."""
    dev = _first_device(capture["planes"])
    thread = dispatch_thread(capture["threads"])
    if dev is None or not thread:
        return None
    ops = dev.get(trace_lib.OPS_LINE) or dev.get(trace_lib.MODULES_LINE)
    if not ops:
        return None
    busy = _merge([(s, s + d) for _, s, d in ops])
    starts = [busy[0][0]] + [s for _, s, _ in thread]
    ends = [busy[-1][1]] + [s + d for _, s, d in thread]
    w0, w1 = min(starts), max(ends)
    gaps, at = [], w0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if w1 > at:
        gaps.append((at, w1))
    out: Dict[Any, float] = {}
    for name in names:
        open_ = _merge([(s, s + d) for n, s, d in thread if n == name])
        out[name] = _overlap(gaps, open_) / 1e9
    listed = set(names)
    any_open = _merge([(s, s + d) for n, s, d in thread if n in listed])
    idle = sum(e - s for s, e in gaps)
    out[None] = (idle - _overlap(gaps, any_open)) / 1e9
    out["idle_s"] = idle / 1e9
    out["window_s"] = (w1 - w0) / 1e9
    return out


def scope_map(hlo_text: str) -> Dict[str, List[str]]:
    """{instruction name: the parts of its op_name} from a compiled
    program's text.  A transform wraps the scopes under it
    (`vmap(topk)/top_k`), so a path splits on brackets as on `/`."""
    out: Dict[str, List[str]] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        if op is not None:
            out[m.group(1)] = [p for p in re.split(r"[/()]+", op.group(1))
                               if p]
    return out


def instruction(event_name: str) -> str:
    """`%sort.57 = (f32[...]) sort(...)` -> `%sort.57`."""
    return event_name.split(" = ", 1)[0].strip()


def self_times(ops: Sequence[Event]) -> List[Tuple[str, float, float]]:
    """(name, start_ns, self_ns) of each event: its duration less that of
    the events directly nested in it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_ns = [ops[i][2] for i in range(len(ops))]
    stack: List[int] = []
    for i in order:
        _, start, dur = ops[i]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= dur
        stack.append(i)
    return [(ops[i][0], ops[i][1], self_ns[i]) for i in range(len(ops))]


def program_ops(dev: Dict[str, List[Event]], program: str,
                ) -> Tuple[int, List[Tuple[str, float, float]]]:
    """(runs of the programs matching `program`, the (name, start_ns,
    self_ns) of the operations inside those runs) on one device."""
    rx = re.compile(program)
    mine = [(s, s + d) for n, s, d in dev.get(trace_lib.MODULES_LINE, [])
            if rx.search(trace_lib.program_name(n))]
    runs, inside, r = _merge(mine), [], 0
    for op in sorted(self_times(dev.get(trace_lib.OPS_LINE, [])),
                     key=lambda e: e[1]):
        while r < len(runs) and runs[r][1] <= op[1]:
            r += 1
        if r < len(runs) and op[1] >= runs[r][0]:
            inside.append(op)  # else an operation of another program
    return len(mine), inside


def scope_seconds(capture: Dict[str, Any], scopes_of: Dict[str, List[str]],
                  program: str, partition: Sequence[str],
                  ) -> Optional[Dict[Any, float]]:
    """{scope: self seconds claimed, None: self seconds no scope of
    `partition` claims, "calls"} over the runs of the programs matching
    `program` on the first device — or None where no such program ran."""
    dev = _first_device(capture["planes"])
    if dev is None:
        return None
    calls, ops = program_ops(dev, program)
    if not calls:
        return None
    out: Dict[Any, float] = {s: 0.0 for s in partition}
    out[None] = 0.0
    part = set(partition)
    for name, _, ns in ops:
        claimed = [p for p in scopes_of.get(instruction(name), ())
                   if p in part]
        out[claimed[-1] if claimed else None] += ns / 1e9
    out["calls"] = calls
    return out


def _memo(ctx: Dict[str, Any], key, make):
    """One reading of the capture for all the metrics that share it."""
    memo = ctx.setdefault("_splits", {})
    if key not in memo:
        memo[key] = make()
    return memo[key]


def scope_reading(src: Dict[str, Any], ctx: Dict[str, Any],
                  ) -> Optional[Dict[Any, float]]:
    """`scope_seconds` for a source that names "program", "scope" and
    optionally the whole partition "scopes", over ctx["capture"] and
    ctx["slot_step_hlo"] — or None where there is no capture, no text,
    no run of the program, or none of the partition's scopes in it."""
    capture, hlo = ctx.get("capture"), ctx.get("slot_step_hlo")
    if not capture or not hlo:
        return None
    partition = tuple(src.get("scopes") or [src["scope"]])
    scopes_of = _memo(ctx, "scope_map", lambda: scope_map(hlo))
    r = _memo(ctx, ("scope", src["program"], partition),
              lambda: scope_seconds(capture, scopes_of, src["program"],
                                    partition))
    if r is None or not any(r[s] > 0 for s in partition):
        return None  # the program carries none of these scopes
    return r


def read(src: Dict[str, Any], ctx: Dict[str, Any]) -> Optional[float]:
    """A metric file's "source" of kind trace_phase or trace_scope over
    ctx["capture"] (`load`) and ctx["slot_step_hlo"] (the compiled slot
    step's text).  None where there is nothing to read."""
    capture = ctx.get("capture")
    if not capture:
        return None
    kind = src["kind"]
    if kind == "trace_phase":
        names = src["none_of"] if src.get("phase") is None else [src["phase"]]
        r = idle_by_phase(capture, names)
        if r is None or r["window_s"] <= 0:
            return None
        return r[src.get("phase")] / r["window_s"] * 100.0
    if kind == "trace_scope":
        r = scope_reading(src, ctx)
        return None if r is None else r[src.get("scope")] / r["calls"] * 1e3
    raise KeyError(f"unknown reader kind {kind!r}")
