"""Doc-drift gate (ISSUE 9 satellite): OBSERVABILITY.md's metric
inventory is load-bearing documentation — this test greps the
instrumented call sites and fails when the two drift, in either
direction:

  * a metric emitted in code but absent from the inventory table
    (undocumented telemetry), or
  * an inventory row naming a metric no code emits (stale row).

Literal names only: dynamically-scoped families (f-string names like
``resilience/<name>/retries_total``) are covered by the inventory's
``resilience/*`` wildcard row and excluded below.
"""

import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "textsummarization_on_flink_tpu"
DOC = REPO / "OBSERVABILITY.md"

#: prefixes the inventory documents as a wildcard family rather than
#: row-per-metric (the resilience/* row points at RESILIENCE.md)
WILDCARD_PREFIXES = ("resilience/",)

#: a metric name as this repo spells them: <layer>/<name>
NAME_RE = re.compile(r"^[a-z]+/[A-Za-z0-9_./]+$")

#: literal first-argument of a counter/gauge/histogram call (f-strings
#: and computed names never match — by design, see module docstring)
EMIT_RE = re.compile(r'(?:counter|gauge|histogram)\(\s*"([^"{}]+)"')


def _package_sources():
    return [p for p in PKG.rglob("*.py") if "__pycache__" not in p.parts]


def emitted_metric_names():
    names = set()
    for path in _package_sources():
        for m in EMIT_RE.finditer(path.read_text(encoding="utf-8")):
            name = m.group(1)
            if NAME_RE.match(name):
                names.add(name)
    assert len(names) > 50, "emit-site scan looks broken"
    return names


def inventory_table_names():
    """Backticked metric names from the doc's inventory table rows
    (lines between the 'Current inventory:' marker and the next ##
    heading)."""
    lines = DOC.read_text(encoding="utf-8").splitlines()
    start = next(i for i, ln in enumerate(lines)
                 if "Current inventory" in ln)
    names = set()
    for ln in lines[start:]:
        if ln.startswith("## "):
            break
        if not ln.lstrip().startswith("|"):
            continue
        for tok in re.findall(r"`([^`]+)`", ln):
            if NAME_RE.match(tok) and "*" not in tok:
                names.add(tok)
    assert len(names) > 40, "inventory-table scan looks broken"
    return names


def test_every_emitted_metric_is_documented():
    doc_names = inventory_table_names()
    undocumented = sorted(
        n for n in emitted_metric_names()
        if n not in doc_names
        and not any(n.startswith(p) for p in WILDCARD_PREFIXES))
    assert not undocumented, (
        f"metrics emitted in code but missing from OBSERVABILITY.md's "
        f"inventory table: {undocumented} — add a row (or a wildcard "
        f"family entry) for each")


def test_no_stale_inventory_rows():
    """Every inventory row's metric must appear as a quoted literal
    somewhere in the package (this catches renamed/deleted metrics whose
    doc row survived)."""
    sources = "\n".join(p.read_text(encoding="utf-8")
                        for p in _package_sources())
    stale = sorted(n for n in inventory_table_names()
                   if f'"{n}"' not in sources)
    assert not stale, (
        f"OBSERVABILITY.md inventory rows with no emitting call site "
        f"left in the package: {stale} — delete or fix the rows")


def test_wildcard_families_really_exist():
    """The wildcard rows must stay honest too: at least one dynamic
    emit site per documented family prefix."""
    sources = "\n".join(p.read_text(encoding="utf-8")
                        for p in _package_sources())
    for prefix in WILDCARD_PREFIXES:
        assert f'f"{prefix}' in sources or f'"{prefix}' in sources, (
            f"no emit sites under the documented wildcard family "
            f"{prefix}*")


@pytest.mark.parametrize("span_name", [
    "serve/dispatch", "decode/batch", "serve/dispatch/wait_mask",
    "train/metrics_flush", "serve/harvest/resolve", "decode/unpack/dispatch",
    "decode/unpack/fetch", "decode/unpack/result", "decode/prefill/batch",
    "decode/prefill/dispatch",
])
def test_documented_span_names_exist_in_code(span_name):
    """The doc's span-name list points at real span call sites."""
    doc = DOC.read_text(encoding="utf-8")
    assert f"`{span_name}`" in doc, f"{span_name} missing from doc"
    sources = "\n".join(p.read_text(encoding="utf-8")
                        for p in _package_sources())
    assert f'"{span_name}"' in sources
