"""scripts/trace_summary.py: the offline summarizer for TS_PROFILE_DIR
captures (the summary names the bottleneck op per device lane)."""

import gzip
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

import trace_summary  # noqa: E402


def _write_trace(path, events):
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)


@pytest.fixture
def trace_dir(tmp_path):
    d = tmp_path / "cap" / "plugins" / "profile" / "2026_07_31_00_00_00"
    d.mkdir(parents=True)
    _write_trace(d / "vm.trace.json.gz", [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 2, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        # device OP line: fusion dominates
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.42",
         "ts": 0, "dur": 900.0},
        {"ph": "X", "pid": 1, "tid": 1, "name": "fusion.42",
         "ts": 1000, "dur": 600.0},
        {"ph": "X", "pid": 1, "tid": 1, "name": "copy.3",
         "ts": 2000, "dur": 100.0},
        # device MODULE line: one enclosing event spanning the same wall
        # time — must NOT be summed into the op line (double count)
        {"ph": "X", "pid": 1, "tid": 2, "name": "jit_multi",
         "ts": 0, "dur": 2100.0},
        # host lane: one op event + python frames (dropped by default)
        {"ph": "X", "pid": 2, "tid": 9, "name": "PjitFunction(multi)",
         "ts": 0, "dur": 50.0},
        {"ph": "X", "pid": 2, "tid": 9, "name": "$threading.py:323 wait",
         "ts": 0, "dur": 5000.0},
        # non-X events are ignored
        {"ph": "B", "pid": 1, "tid": 1, "name": "ignored", "ts": 0},
    ])
    return tmp_path / "cap"


def test_summarize_groups_ops_per_thread_lane_and_drops_host_frames(
        trace_dir):
    files = trace_summary.find_trace_files(str(trace_dir))
    assert len(files) == 1
    lanes = trace_summary.summarize(trace_summary.load_events(files[0]))
    assert [lane["lane"] for lane in lanes] == [
        "/device:TPU:0/XLA Modules", "/device:TPU:0/XLA Ops", "/host:CPU"]
    mod, dev, host = lanes
    # the module line stays its own lane: its enclosing event neither
    # inflates the op line's busy time nor tops its op table
    assert mod["ops"] == [{"name": "jit_multi", "total_us": 2100.0,
                           "count": 1}]
    # fusion.42 aggregated across occurrences, ops sorted by total time
    assert dev["ops"][0] == {"name": "fusion.42", "total_us": 1500.0,
                             "count": 2}
    assert dev["ops"][1]["name"] == "copy.3"
    assert dev["busy_us"] == 1600.0
    # the $python-frame event is dropped: busy time counts real ops only
    assert [op["name"] for op in host["ops"]] == ["PjitFunction(multi)"]
    assert host["busy_us"] == 50.0
    # opt-in keeps the frames
    lanes_all = trace_summary.summarize(
        trace_summary.load_events(files[0]), include_host_frames=True)
    host_all = next(lane for lane in lanes_all if lane["pid"] == 2)
    assert host_all["busy_us"] == 5050.0


def test_cli_renders_table_and_json(trace_dir, capsys):
    assert trace_summary.main([str(trace_dir), "--top", "1"]) == 0
    out = capsys.readouterr().out
    assert "fusion.42" in out and "/device:TPU:0" in out
    assert "copy.3" not in out  # --top 1
    assert trace_summary.main([str(trace_dir), "--json"]) == 0
    rec = json.loads(capsys.readouterr().out)
    ops_lane = next(lane for lane in rec["lanes"]
                    if lane["lane"].endswith("XLA Ops"))
    assert ops_lane["ops"][0]["name"] == "fusion.42"


def test_cli_errors_without_capture(tmp_path, capsys):
    assert trace_summary.main([str(tmp_path)]) == 1
    assert "capture" in capsys.readouterr().err


# -- request timelines (ISSUE 9 satellite) ---------------------------------

@pytest.fixture
def events_file(tmp_path):
    t0 = 1_700_000_000_000_000
    recs = [
        {"kind": "request", "event": "enqueue", "uuid": "u7",
         "trace_id": "t7", "span_id": "s7", "ts_us": t0,
         "attrs": {"depth": 1}},
        {"kind": "request", "event": "admit", "uuid": "u7",
         "trace_id": "t7", "span_id": "s7", "ts_us": t0 + 2_000,
         "attrs": {"queue_ms": 2.0}},
        {"kind": "request", "event": "slot", "uuid": "u7",
         "trace_id": "t7", "span_id": "s7", "ts_us": t0 + 2_100,
         "attrs": {"slot": 3, "tick": 9}},
        {"kind": "span", "name": "serve/dispatch", "trace_id": "t7",
         "span_id": "sp1", "ts_us": t0 + 2_200, "dur_us": 1_000,
         "pid": 1, "tid": 1},
        {"kind": "request", "event": "finish", "uuid": "u7",
         "trace_id": "t7", "span_id": "s7", "ts_us": t0 + 9_000,
         "attrs": {"chunks": 4}},
        {"kind": "request", "event": "resolve", "uuid": "u7",
         "trace_id": "t7", "span_id": "s7", "ts_us": t0 + 9_500},
        # a NEIGHBOR request: must not leak into u7's timeline
        {"kind": "request", "event": "enqueue", "uuid": "u8",
         "trace_id": "t8", "span_id": "s8", "ts_us": t0 + 100},
        # scalar record + junk line tolerance
        {"step": 3, "loss": 2.5},
    ]
    p = tmp_path / "events.jsonl"
    with open(p, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
        f.write("{broken tail\n")
    return p


class TestRequestTimeline:
    def test_reconstructs_phases_and_spans(self, events_file):
        tl = trace_summary.request_timeline([str(events_file)], "u7")
        assert [e["event"] for e in tl["events"]] == [
            "enqueue", "admit", "slot", "finish", "resolve"]
        assert tl["trace_id"] == "t7"
        assert tl["phases"] == {"queue_ms": 2.0, "resident_ms": 7.0,
                                "resolve_ms": 0.5, "total_ms": 9.5}
        # the trace's spans ride along; the neighbor's do not
        assert [s["name"] for s in tl["spans"]] == ["serve/dispatch"]

    def test_evicted_request_resident_falls_back_to_resolve(self, tmp_path):
        recs = [
            {"kind": "request", "event": "enqueue", "uuid": "u1",
             "trace_id": "t1", "span_id": "s1", "ts_us": 1_000_000},
            {"kind": "request", "event": "admit", "uuid": "u1",
             "trace_id": "t1", "span_id": "s1", "ts_us": 1_500_000},
            {"kind": "request", "event": "evict", "uuid": "u1",
             "trace_id": "t1", "span_id": "s1", "ts_us": 1_600_000},
            {"kind": "request", "event": "resolve", "uuid": "u1",
             "trace_id": "t1", "span_id": "s1", "ts_us": 1_700_000,
             "attrs": {"error": "DeadlineExceededError"}},
        ]
        p = tmp_path / "events.jsonl"
        p.write_text("".join(json.dumps(r) + "\n" for r in recs))
        tl = trace_summary.request_timeline([str(p)], "u1")
        assert tl["phases"]["resident_ms"] == 200.0  # admit -> resolve
        assert "resolve_ms" not in tl["phases"]
        assert tl["phases"]["total_ms"] == 700.0

    def test_cli_text_and_json(self, events_file, capsys):
        assert trace_summary.main(
            [str(events_file), "--request", "u7"]) == 0
        out = capsys.readouterr().out
        assert "request 'u7' (trace t7)" in out
        assert "slot (slot=3, tick=9)" in out
        assert "queue 2.000 ms" in out and "total 9.500 ms" in out
        assert "serve/dispatch" in out
        assert trace_summary.main(
            [str(events_file), "--request", "u7", "--json"]) == 0
        tl = json.loads(capsys.readouterr().out)
        assert tl["phases"]["total_ms"] == 9.5

    def test_cli_directory_argument(self, events_file, capsys):
        assert trace_summary.main(
            [str(events_file.parent), "--request", "u8", "--json"]) == 0
        tl = json.loads(capsys.readouterr().out)
        assert [e["event"] for e in tl["events"]] == ["enqueue"]

    def test_unknown_uuid_errors(self, events_file, capsys):
        assert trace_summary.main(
            [str(events_file), "--request", "nope"]) == 1
        assert "no request events" in capsys.readouterr().err

    def test_no_events_jsonl_errors(self, tmp_path, capsys):
        assert trace_summary.main(
            [str(tmp_path), "--request", "u1"]) == 1
        assert "events.jsonl" in capsys.readouterr().err
