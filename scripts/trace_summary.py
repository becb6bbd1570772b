#!/usr/bin/env python
"""Summarize a trace capture into an op-level table (top ops by device
time, per lane).

    python scripts/trace_summary.py [exp/trace_r05] [--top 15] [--json]
    python scripts/trace_summary.py logs/exp/train/events.jsonl
    python scripts/trace_summary.py exp/serve/events.jsonl --request u17

Two capture kinds, one tool (ISSUE 1 satellite):

  * Chrome-trace JSON (`*.trace.json[.gz]`) that `jax.profiler` writes
    next to the xplane file (TensorBoard not required — the rig has no
    tensorboard_plugin_profile, so this parses the portable format);
  * the unified obs `events.jsonl` (obs/export.py EventSink +
    SummaryWriter scalars in one file): `{"kind": "span", ...}` records
    are treated as complete events; scalar/step records are skipped.

Events are grouped into lanes (one per process/pid: TPU device lanes,
host threads); within a lane, complete events ('ph': 'X') are summed by
name.  Python host-frame events (names like `$threading.py:323 wait`)
are dropped from per-op tables by default — on a device lane the names
are XLA ops/fusions, which is the table that names the bottleneck op
(e.g. where a low-MFU transformer step spends its time).

Directory arguments prefer profiler captures when both kinds are
present (the established behavior); point at the events.jsonl file
directly — or a directory holding only events.jsonl — for span tables.

``--request <uuid-or-trace_id>`` switches to the request-timeline view
(ISSUE 9): the ``{"kind": "request"}`` lifecycle events the serve path
emits (enqueue -> admit -> slot -> finish -> resolve, OBSERVABILITY.md
"Request-scoped tracing") are reconstructed for one uuid, printed with
per-phase durations (queue wait vs resident/decode vs resolve fan-out),
plus any spans stamped with the request's trace_id.  A TRACE id works
too (ISSUE 15): paste a histogram bucket's exemplar straight off
``/metrics`` or ``/exemplars`` and the fat-p99 request's full
cross-replica timeline comes back.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
from collections import defaultdict


def find_trace_files(root: str) -> list:
    """Candidate captures under `root`: profiler Chrome traces when any
    exist (established behavior), else unified obs events.jsonl files."""
    if os.path.isfile(root):
        return [root]
    pats = [os.path.join(root, "**", "*.trace.json.gz"),
            os.path.join(root, "**", "*.trace.json")]
    files: list = []
    for p in pats:
        files.extend(glob.glob(p, recursive=True))
    if files:
        return sorted(files)
    return sorted(glob.glob(os.path.join(root, "**", "events.jsonl"),
                            recursive=True))


def _events_jsonl_to_trace(path: str) -> dict:
    """Unified events.jsonl -> the Chrome-trace dict shape summarize()
    consumes.  Span records become 'X' complete events; SummaryWriter
    scalar records ({"step": N, ...}) and snapshot dumps are skipped."""
    events: list = []
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # half-written tail line of a live run
            if not isinstance(rec, dict) or rec.get("kind") != "span":
                continue
            events.append({
                "ph": "X",
                "name": rec.get("name", "?"),
                "ts": float(rec.get("ts_us", 0)),
                "dur": float(rec.get("dur_us", 0)),
                "pid": rec.get("pid", 0),
                "tid": rec.get("tid", 0),
            })
    return {"traceEvents": events}


def load_events(path: str) -> dict:
    if path.endswith(".jsonl"):
        return _events_jsonl_to_trace(path)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def summarize(trace: dict, include_host_frames: bool = False) -> list:
    """Per-lane op-time summary, one lane per (pid, tid) thread line.

    Grouping by pid alone would double-count: the profiler's export
    gives a device several lines (e.g. a module/step-level line whose
    events span the same wall time as the per-op line), so summing
    across a pid's tids inflates busy time and the enclosing module
    event would top the \"op\" table.  Per-thread lanes keep each line
    honest; the op line is the one whose names are XLA ops/fusions.

    Returns [{lane, pid, tid, busy_us, ops: [{name, total_us, count}]}]
    sorted by lane busy time, descending.
    """
    proc_names: dict = {}
    thread_names: dict = {}
    for e in trace.get("traceEvents", []):
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            proc_names[e.get("pid")] = e.get("args", {}).get("name", "?")
        elif e.get("name") == "thread_name":
            thread_names[(e.get("pid"), e.get("tid"))] = (
                e.get("args", {}).get("name", "?"))

    per_lane: dict = defaultdict(lambda: defaultdict(lambda: [0.0, 0]))
    busy: dict = defaultdict(float)
    for e in trace.get("traceEvents", []):
        if e.get("ph") != "X":
            continue
        name = e.get("name", "?")
        if not include_host_frames and name.startswith("$"):
            continue  # python host frames, not ops
        dur = float(e.get("dur", 0.0))
        key = (e.get("pid"), e.get("tid"))
        cell = per_lane[key][name]
        cell[0] += dur
        cell[1] += 1
        busy[key] += dur
    out = []
    for (pid, tid), ops in per_lane.items():
        proc = proc_names.get(pid, str(pid))
        thread = thread_names.get((pid, tid))
        out.append({
            "lane": f"{proc}/{thread}" if thread else proc,
            "pid": pid,
            "tid": tid,
            "busy_us": round(busy[(pid, tid)], 1),
            "ops": sorted(
                ({"name": n, "total_us": round(t, 1), "count": c}
                 for n, (t, c) in ops.items()),
                key=lambda o: -o["total_us"]),
        })
    out.sort(key=lambda lane: -lane["busy_us"])
    return out


def _iter_jsonl(path: str):
    """Parsed records of one events.jsonl (bad/half-written lines
    skipped, same tolerance as _events_jsonl_to_trace)."""
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict):
                yield rec


def request_timeline(paths, uuid: str) -> dict:
    """One request's reconstructed timeline from unified events.jsonl
    file(s): its lifecycle events (by uuid — or by trace_id, so a
    histogram EXEMPLAR off /metrics or /exemplars pastes straight in,
    ISSUE 15), the spans sharing its trace_id, and the per-phase
    durations.

    Returns {"uuid", "trace_id", "events": [...], "spans": [...],
    "phases": {...}, "children": [...]} — events/spans sorted by ts_us.
    Phases (ms): ``queue`` = enqueue->admit, ``resident`` =
    admit->finish (or ->resolve when no finish event exists, e.g. a
    queue eviction), ``resolve`` = finish->resolve, ``total`` =
    enqueue->resolve.

    ``children`` (ISSUE 19): when the uuid is a HIERARCHICAL document
    request (serve/hiersum.py), every chunk and reduce sub-request
    shares the parent's trace_id and carries a ``hier_chunk`` /
    ``hier_reduce`` lifecycle event — those sub-requests come back as
    one entry each (chunk index, bucket, tier, cache_hit, resident ms
    from the child's own admit->finish window) so the whole fan-out
    tree reconstructs from one events.jsonl.  Empty for plain requests.
    """
    # pass 1: the uuid's (or exemplar trace_id's) request events (tiny
    # result set).  Buffering the file's spans instead would hold
    # memory proportional to the whole capture just to answer one uuid.
    events: list = []
    for path in paths:
        events.extend(r for r in _iter_jsonl(path)
                      if r.get("kind") == "request"
                      and (r.get("uuid") == uuid
                           or r.get("trace_id") == uuid))
    events.sort(key=lambda r: r.get("ts_us", 0))
    # the argument may have been a trace_id: resolve the uuid the
    # matched lifecycle events actually carry (first one wins — a
    # trace_id maps to one routed request by construction)
    uuids = [r["uuid"] for r in events if r.get("uuid")]
    if uuids and uuid not in uuids:
        uuid = uuids[0]
    trace_ids = {r["trace_id"] for r in events if r.get("trace_id")}
    trace_id = sorted(trace_ids)[0] if trace_ids else None
    # pass 2 (only when the uuid matched a trace): spans sharing its
    # trace_ids.  A cheap substring pre-filter skips the JSON decode
    # for the vast majority of non-matching lines, so the second pass
    # costs ~one scan, with memory bounded by the MATCHING spans.
    spans: list = []
    if trace_ids:
        for path in paths:
            with open(path, "r", encoding="utf-8") as f:
                for line in f:
                    if '"span"' not in line or not any(
                            tid in line for tid in trace_ids):
                        continue
                    try:
                        r = json.loads(line)
                    except ValueError:
                        continue
                    if (isinstance(r, dict) and r.get("kind") == "span"
                            and r.get("trace_id") in trace_ids):
                        spans.append(r)
        spans.sort(key=lambda r: r.get("ts_us", 0))
    first = {}
    resolves: list = []
    for r in events:  # first occurrence of each lifecycle stage wins...
        if r.get("event") == "resolve":
            resolves.append(r)
            continue
        first.setdefault(r.get("event"), r.get("ts_us", 0))
    # ...except resolve: a fleet-routed uuid resolves a replica-level
    # future per attempt (a killed replica's typed rejection, a hedge
    # loser) before the ROUTER future settles — the terminal resolve is
    # the one tagged scope=fleet when present, else the last one seen
    # (plain single-server timelines have exactly one either way)
    if resolves:
        tagged = [r for r in resolves
                  if (r.get("attrs") or {}).get("scope")]
        first["resolve"] = (tagged[-1] if tagged
                            else resolves[-1]).get("ts_us", 0)
    phases = {}

    def _ms(a, b):
        return round((first[b] - first[a]) / 1e3, 3)

    if "enqueue" in first and "admit" in first:
        phases["queue_ms"] = _ms("enqueue", "admit")
    if "admit" in first:
        if "finish" in first:
            phases["resident_ms"] = _ms("admit", "finish")
        elif "resolve" in first:
            phases["resident_ms"] = _ms("admit", "resolve")
    if "finish" in first and "resolve" in first:
        phases["resolve_ms"] = _ms("finish", "resolve")
    # a request's timeline ROOT is its first lifecycle event: enqueue
    # for a queued request, else coalesced (a follower attached to an
    # in-flight leader) or cache_hit (resolved synchronously at submit)
    # — the ISSUE-14 front-door paths never enqueue (SERVING.md "Front
    # door"), but their coalesced/cache_hit -> resolve window is still
    # the caller-observed total
    root = next((e for e in ("enqueue", "coalesced", "cache_hit")
                 if e in first), None)
    if root is not None and "resolve" in first:
        phases["total_ms"] = _ms(root, "resolve")
    # the hier fan-out tree: a document parent's chunk/reduce
    # sub-requests ride the SAME trace_id under their own uuids, each
    # self-identifying with a hier_chunk/hier_reduce event — group the
    # trace's OTHER uuids and keep exactly those (a hedged or
    # fleet-routed plain request re-emits under its own uuid and is
    # never mistaken for a child)
    children: list = []
    if trace_ids:
        by_uuid: dict = defaultdict(list)
        for path in paths:
            for r in _iter_jsonl(path):
                if (r.get("kind") == "request"
                        and r.get("trace_id") in trace_ids
                        and r.get("uuid") not in (uuid, None, "")):
                    by_uuid[r["uuid"]].append(r)
        for child_uuid, evs in by_uuid.items():
            evs.sort(key=lambda r: r.get("ts_us", 0))
            hier = next((r for r in evs if r.get("event")
                         in ("hier_chunk", "hier_reduce")), None)
            if hier is None:
                continue
            attrs = hier.get("attrs") or {}
            cfirst: dict = {}
            for r in evs:
                cfirst.setdefault(r.get("event"), r.get("ts_us", 0))
            resident = None
            if "admit" in cfirst:
                end = cfirst.get("finish", cfirst.get("resolve"))
                if end is not None:
                    resident = round((end - cfirst["admit"]) / 1e3, 3)
            children.append({
                "uuid": child_uuid,
                "kind": ("reduce" if hier.get("event") == "hier_reduce"
                         else "chunk"),
                "chunk": attrs.get("chunk"),
                "bucket": attrs.get("bucket"),
                "tier": attrs.get("tier"),
                "cache_hit": bool(attrs.get("cache_hit")),
                "resident_ms": resident,
            })
        children.sort(key=lambda c: (c["kind"] == "reduce",
                                     c["chunk"] if c["chunk"] is not None
                                     else 1 << 30, c["uuid"]))
    return {"uuid": uuid, "trace_id": trace_id, "events": events,
            "spans": spans, "phases": phases,
            "children": children, "trace_ids": sorted(trace_ids)}


def print_request_timeline(tl: dict) -> int:
    if not tl["events"]:
        print(f"no request events for uuid {tl['uuid']!r} — was the run "
              f"writing a unified events.jsonl (obs.install_event_sink / "
              f"TS_OBS_EVENTS=1, OBSERVABILITY.md)?", file=sys.stderr)
        return 1
    print(f"request {tl['uuid']!r} (trace {tl['trace_id']}):")
    t0 = tl["events"][0].get("ts_us", 0)
    for r in tl["events"]:
        attrs = r.get("attrs") or {}
        extra = (" (" + ", ".join(f"{k}={v}" for k, v in attrs.items())
                 + ")") if attrs else ""
        print(f"  +{(r.get('ts_us', 0) - t0) / 1e3:>9.3f} ms "
              f"{r.get('event')}{extra}")
    if tl["phases"]:
        print("phases: " + " | ".join(
            f"{k[:-3]} {v:.3f} ms" for k, v in tl["phases"].items()))
    if tl.get("children"):
        kids = tl["children"]
        n_chunks = sum(1 for c in kids if c["kind"] == "chunk")
        n_red = len(kids) - n_chunks
        print(f"fan-out ({n_chunks} chunk{'s' if n_chunks != 1 else ''}"
              + (f" + {n_red} reduce" if n_red else "") + "):")
        for i, c in enumerate(kids):
            branch = "└─" if i == len(kids) - 1 else "├─"
            label = (f"reduce" if c["kind"] == "reduce"
                     else f"chunk {c['chunk']}")
            cost = ("cache hit" if c["cache_hit"]
                    else (f"resident {c['resident_ms']:.3f} ms"
                          if c["resident_ms"] is not None else "pending"))
            detail = ", ".join(
                x for x in (f"bucket {c['bucket']}"
                            if c["bucket"] is not None else "",
                            f"tier {c['tier']}" if c["tier"] else "",
                            cost) if x)
            print(f"  {branch} {c['uuid']}  {label}  ({detail})")
    if tl["spans"]:
        print(f"spans in trace ({len(tl['spans'])}):")
        for s in tl["spans"]:
            print(f"  +{(s.get('ts_us', 0) - t0) / 1e3:>9.3f} ms "
                  f"{s.get('name')} ({s.get('dur_us', 0) / 1e3:.3f} ms)")
    if len(tl["trace_ids"]) > 1:
        print(f"WARNING: uuid maps to {len(tl['trace_ids'])} trace_ids "
              f"(resubmitted uuid?): {tl['trace_ids']}", file=sys.stderr)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir", nargs="?", default="exp/trace_r05")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--host-frames", action="store_true",
                    help="keep $file:line python-frame events")
    ap.add_argument("--request", metavar="UUID_OR_TRACE_ID", default=None,
                    help="reconstruct ONE request's lifecycle timeline "
                         "(enqueue->admit->slot->finish->resolve) from "
                         "unified events.jsonl instead of the op table; "
                         "accepts a uuid or a trace_id (e.g. a histogram "
                         "exemplar off /metrics or /exemplars)")
    args = ap.parse_args(argv)

    if args.request is not None:
        jsonl = [p for p in find_trace_files(args.trace_dir)
                 if p.endswith(".jsonl")]
        if not jsonl:
            # a directory holding profiler captures only: look for the
            # events.jsonl family explicitly (request events live there)
            jsonl = sorted(glob.glob(
                os.path.join(args.trace_dir, "**", "events.jsonl"),
                recursive=True)) if os.path.isdir(args.trace_dir) else []
        if not jsonl:
            print(f"no events.jsonl under {args.trace_dir} — request "
                  f"timelines need the unified event stream "
                  f"(OBSERVABILITY.md)", file=sys.stderr)
            return 1
        tl = request_timeline(jsonl, args.request)
        if args.json:
            print(json.dumps(tl))
            return 0 if tl["events"] else 1
        return print_request_timeline(tl)

    files = find_trace_files(args.trace_dir)
    if not files:
        print(f"no *.trace.json[.gz] or events.jsonl under "
              f"{args.trace_dir} — capture a profiler trace "
              f"(HParams(profile_dir=...)) or run with obs enabled "
              f"(OBSERVABILITY.md)", file=sys.stderr)
        return 1
    path = files[-1]  # newest capture wins (sorted paths are dated)
    lanes = summarize(load_events(path), args.host_frames)
    if args.json:
        print(json.dumps({"trace": path, "lanes": [
            {**lane, "ops": lane["ops"][:args.top]} for lane in lanes]}))
        return 0
    print(f"trace: {path}")
    for lane in lanes:
        if not lane["ops"]:
            continue
        print(f"\nlane {lane['lane']!r} (pid {lane['pid']} "
              f"tid {lane['tid']}, busy {lane['busy_us'] / 1e3:.1f} ms):")
        for op in lane["ops"][:args.top]:
            pct = 100.0 * op["total_us"] / max(lane["busy_us"], 1e-9)
            print(f"  {op['total_us'] / 1e3:>9.2f} ms {pct:>5.1f}%  "
                  f"x{op['count']:<5} {op['name'][:80]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
