"""The generic per-layer readers.  A per-layer metric is a data file
under benchmark/metrics/ whose "source" names one of the kinds below and
its parameters; a metric over a NEW counter, histogram, phase, scope,
program or count function of an existing kind is therefore a new file
(and, for a count, a function `count_<name>` in the family's module),
not code here.

A reader that finds nothing to read returns None and the harness leaves
the metric out of the line.  It never returns 0 for a share of a
roofline or of a peak.

Kinds:
  registry        a series of the program's obs registry over the window
                  (histogram: sum | count | mean | p50 | p95 | p99;
                  counter: the increase), times "scale", optionally
                  divided by the window's seconds ("per_window_s")
  registry_ratio  sum of histogram sums (or counter increases) over
                  another, or over the window; the phase ledger's phases
                  are the series `profile/phase_seconds|phase=<name>` and
                  its walls `profile/wall_seconds|wall=<name>`
  harness         a number the harness itself measured (the generator's
                  lateness, ...)
  trace_program   device time of the jitted programs whose name matches
                  "pattern": mean_ms | total_s | calls
  trace_device    idle_pct of the traced window
  roofline        least time for the named count function's FLOPs and
                  bytes at the chip's peaks, over the traced device time
                  of the matching program
  mfu             FLOPs of the named count functions times the calls of
                  their programs in the traced window, over the peak
                  times "over": window | busy
  trace_phase     idle share of the capture while a host phase is open
  trace_scope     a named scope's device self time a program run (both in
                  splits.py, over the capture itself)
  scope_roofline  least time for the named count function's FLOPs and
                  bytes at the chip's peaks, over the device self time of
                  "scope" in the runs of the programs matching "program"
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from harness import counts as counts_lib
from harness import reference, splits
from harness import trace as trace_lib


def series_key(spec: Dict[str, Any]) -> str:
    return spec["name"] + "".join(
        f"|{k}={v}" for k, v in sorted(spec.get("labels", {}).items()))


def hist_delta(ctx, key) -> Optional[Dict[str, Any]]:
    b = ctx["registry1"].get(key)
    if not isinstance(b, dict):
        return None
    a = ctx["registry0"].get(key)
    if not isinstance(a, dict):
        a = {"count": 0, "sum": 0.0, "counts": [0] * len(b["counts"])}
    return {"count": b["count"] - a["count"], "sum": b["sum"] - a["sum"],
            "buckets": b["buckets"], "max": b["max"], "min": b["min"],
            "counts": [y - x for x, y in zip(a["counts"], b["counts"])]}


def hist_percentile(h: Dict[str, Any], q: float) -> Optional[float]:
    """Percentile from bucket counts, interpolated inside the bucket (the
    registry's own rule, applied to the window's share of the counts)."""
    total = h["count"]
    if total <= 0:
        return None
    rank, cum = q / 100.0 * total, 0
    for i, c in enumerate(h["counts"]):
        if c <= 0:
            continue
        if cum + c >= rank:
            lo = h["buckets"][i - 1] if i > 0 else 0.0
            hi = h["buckets"][i] if i < len(h["buckets"]) else (
                h["max"] if h["max"] is not None else lo)
            return lo + (rank - cum) / c * (hi - lo)
        cum += c
    return h["max"]


def _series_total(ctx, spec) -> Optional[float]:
    key = series_key(spec)
    b = ctx["registry1"].get(key)
    if b is None:
        return None
    if isinstance(b, dict):
        d = hist_delta(ctx, key)
        return d["sum"] if d["count"] > 0 else None
    a = ctx["registry0"].get(key, 0.0)
    return float(b) - float(a or 0.0)


class NothingInWindow(LookupError):
    """The window holds nothing of what a count is taken over: its
    metric is left out of the line."""


def occupied_slots(ctx) -> float:
    """The mean number of OCCUPIED slots over the window, from the
    scheduler's own histogram."""
    occ = hist_delta(ctx, "serve/slot_occupancy")
    if occ is None or occ["count"] <= 0:
        raise NothingInWindow("no slot occupancy in the window")
    return occ["sum"] / occ["count"] * float(ctx["deployment"]["slots"])


def _count(name: str, ctx) -> Dict[str, float]:
    """{"flops", "bytes"} of ONE call of the program the count is named
    for.  The shared compositions (counts.py) by name; any other name is
    the family module's `count_<name>(hp, dep, ctx)`; a name nobody has
    is an error.  `NothingInWindow` where the window lacks what a
    count needs."""
    fam = reference.family(ctx["family"])
    hp, dep = ctx["hparams"], ctx["deployment"]
    if name == "train_step":
        return counts_lib.train_step(fam, hp, dep)
    if name == "prefill":
        return counts_lib.prefill(
            fam, hp, dep, float(ctx["harness"]["mean_article_len"]))
    if name == "slot_chunk":
        return counts_lib.slot_chunk(
            fam, hp, dep, occupied_slots(ctx),
            float(ctx["harness"]["mean_article_len"]))
    fn = getattr(fam, "count_" + name, None)
    if fn is None:
        have = sorted(k[6:] for k in vars(fam) if k.startswith("count_"))
        raise ValueError(f"no count function {name!r}: counts.py has "
                         f"train_step, prefill, slot_chunk and "
                         f"{fam.__name__} has {have}")
    return fn(hp, dep, ctx)


def _least_seconds(count: str, ctx) -> Optional[float]:
    """The least time the chip could take for one call's count."""
    try:
        c = _count(count, ctx)
    except NothingInWindow:
        return None
    peaks = ctx["peaks"]
    return max(c["flops"] / peaks["flops_per_s"],
               c["bytes"] / peaks["bytes_per_s"])


def read(spec: Dict[str, Any], ctx: Dict[str, Any]) -> Optional[float]:
    src = spec["source"]
    kind = src["kind"]
    scale = float(src.get("scale", 1.0))
    tr = ctx.get("trace")
    if kind == "harness":
        v = ctx["harness"].get(src["key"])
        return None if v is None else float(v) * scale
    if kind == "registry":
        key = series_key(src)
        stat = src.get("stat", "sum")
        b = ctx["registry1"].get(key)
        if b is None:
            return None
        if isinstance(b, dict):
            d = hist_delta(ctx, key)
            if d["count"] <= 0:
                return None
            v = (d["sum"] if stat == "sum" else d["count"] if stat == "count"
                 else d["sum"] / d["count"] if stat == "mean"
                 else hist_percentile(d, float(stat[1:])))
        else:
            v = _series_total(ctx, src)
        if v is None:
            return None
        if src.get("per_window_s"):
            v = v / ctx["window_s"]
        return v * scale
    if kind == "registry_ratio":
        nums = [_series_total(ctx, s) for s in src["num"]]
        nums = [x for x in nums if x is not None]
        den = (ctx["window_s"] if src["den"] == "window"
               else _series_total(ctx, src["den"]))
        if not nums or not den:
            return None
        return sum(nums) / den * scale
    if kind in ("trace_phase", "trace_scope"):
        return splits.read(src, ctx)
    if kind == "scope_roofline":
        r = splits.scope_reading(src, ctx)
        if r is None or r[src["scope"]] <= 0:
            return None
        least = _least_seconds(src["count"], ctx)
        if not least:
            return None
        return least * r["calls"] / r[src["scope"]] * 100.0
    if tr is None or not tr.get("devices"):
        return None
    peaks = ctx["peaks"]
    if kind == "trace_device":
        if tr["window_s"] <= 0 or tr["busy_s"] <= 0:
            return None
        return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
    if kind == "trace_program":
        m = trace_lib.match_programs(tr["programs"], src["pattern"])
        if m["calls"] <= 0:
            return None
        return float(m[src.get("stat", "mean_ms")]) * scale
    if kind == "roofline":
        m = trace_lib.match_programs(tr["programs"], src["pattern"])
        if m["calls"] <= 0 or m["total_s"] <= 0:
            return None
        least = _least_seconds(src["count"], ctx)
        if least is None:
            return None
        return least * m["calls"] / m["total_s"] * 100.0
    if kind == "mfu":
        flops = 0.0
        for part in src["parts"]:
            m = trace_lib.match_programs(tr["programs"], part["pattern"])
            if m["calls"] <= 0:
                continue
            try:
                flops += _count(part["count"], ctx)["flops"] * m["calls"]
            except NothingInWindow:
                continue
        den = tr["busy_s"] if src.get("over") == "busy" else tr["window_s"]
        if flops <= 0 or den <= 0:
            return None
        return flops / (den * peaks["flops_per_s"]) * 100.0
    raise KeyError(f"unknown reader kind {kind!r}")
