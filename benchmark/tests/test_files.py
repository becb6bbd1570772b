"""BENCHMARK.json and every file under benchmark/ parse and
cross-reference, and keep to the contract's limits on names and units."""
import glob
import json
import os
import re

import numpy as np

from tests.tiny import BENCH, cell_file, cells

ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KINDS = {"registry", "registry_ratio", "harness", "trace_program",
         "trace_device", "roofline", "mfu", "trace_phase", "trace_scope",
         "scope_roofline"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_json_parses():
    files = glob.glob(os.path.join(BENCH, "**", "*.json"), recursive=True)
    assert len(files) > 20
    for path in files:
        with open(path) as f:
            json.load(f)


def test_keys_names_and_units():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark"] and 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        for e in b[group]:
            assert set(e) == keys, e
            assert NAME.match(e["name"]) and len(e["why"]) <= 200
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in b["end_to_end"])


def test_cross_references():
    b = _bench()
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(cells) // 4)
    for c in b["configs"]:
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in b["workloads"])
    for w in b["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            cf = json.load(f)
        assert cf["name"] == w["name"] and cf["limits"] and cf["check"]
    e2e = {m["name"]: m for m in b["end_to_end"]}

    def reports(cell, metric):
        m = e2e[metric]
        return "workloads" not in m or cell in m["workloads"]

    for m in b["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            mf = json.load(f)
        assert mf["name"] == m["name"] and mf["unit"] == m["unit"]
        assert mf["layer"] == m["layer"] and mf["moves"] == m["moves"]
        assert mf["source"]["kind"] in KINDS
        for cell in m["workloads"]:
            assert cell in cells and reports(cell, m["moves"]), (m, cell)
    for name in cells:
        assert sum(reports(name, k) for k in e2e) >= 2
        assert any(name in m.get("workloads", cells) for m in b["per_layer"])
    # a roofline or mfu share travels with the whole step's share
    for cell in cells:
        mine = [m["name"] for m in b["per_layer"] if cell in m["workloads"]]
        assert any("_roofline" in n for n in mine)
        assert any("mfu" in n.split(".")[0].split("_") for n in mine)
        assert any(n.startswith("device_idle") for n in mine)
    # a metric file belongs to a per-layer metric of BENCHMARK.json or of
    # a held cell (benchmark/held/), and so does every other data file
    held = [json.load(open(p)) for p in
            glob.glob(os.path.join(BENCH, "held", "*.json"))]

    def on_disk(sub):
        return {os.path.basename(p)[:-5] for p in
                glob.glob(os.path.join(BENCH, sub, "*.json"))}

    assert on_disk("metrics") == {m["name"] for m in b["per_layer"]} | {
        m["name"] for h in held for m in h["per_layer"]}
    assert on_disk("workloads") == set(cells) | {
        h["workload"]["name"] for h in held}
    assert on_disk("configs") == set(configs) | {
        h["config"]["name"] for h in held}
    assert on_disk("traffic") == {w["traffic"] for w in b["workloads"]} | {
        h["workload"]["traffic"] for h in held}
    # a mix that asks for summary lengths needs a configuration whose
    # weights carry the clock, and a family whose words code each length
    from harness import weights

    for w in b["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        if "summary" in mix:
            with open(os.path.join(ROOT, configs[w["config"]]["file"])) as f:
                cfg = json.load(f)
            fam, clock = weights.summary_clock(cfg)
            spec = mix["summary"]["length"]
            coded = set(fam.length_code(clock, np.arange(
                4, int(cfg["hparams"]["vocab_size"]))).tolist())
            assert set(range(spec["min"], spec["max"] + 1)) <= coded


def test_a_serving_cell_holds_the_numbers_it_samples():
    """Every served cell has the reference score some of what it served
    (`check.sample.score` >= 1 and a `score_gap` limit); it holds the two
    beam numbers if and only if it samples a search of the reference's
    own (`check.sample.beam` >= 1): a limit on a number never measured
    fails every run, a number measured and not held catches nothing."""
    served = cells("open_loop")
    assert served
    for cell, _ in served:
        cf = cell_file(cell)
        sample, limits = cf["check"]["sample"], cf["limits"]
        assert int(sample["score"]) >= 1 and "score_gap" in limits, cell
        beam = {"beam_gap", "beam_gap_median"} & set(limits)
        assert beam == ({"beam_gap", "beam_gap_median"}
                        if int(sample["beam"]) >= 1 else set()), cell
