"""Reading a compiled program's text in tests (no test lives here)."""

import math
import re

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")


def _instructions(text: str) -> list:
    return [m.groups() for m in map(_INSTR.match, text.splitlines()) if m]


def _dims(shape: str) -> list:
    """Every dimension printed in a shape (a tuple's parts together)."""
    return [int(d) for part in re.findall(r"\[([\d,]*)\]", shape)
            for d in part.split(",") if d]


def wide_dimensions(text: str, width: int) -> list:
    """The instructions of ``compiled.as_text()`` whose result has a
    dimension of exactly ``width``: with the extended vocabulary's
    width, whatever still builds or passes on the pointer mixture's
    row."""
    return [f"{name} = {shape} {opcode}(" for name, shape, opcode, _
            in _instructions(text) if width in _dims(shape)]


def wide_scatters(text: str, at_least: int) -> list:
    """The scatters whose result (the operand's shape) has a dimension
    of ``at_least`` or more: a scatter into a vocabulary-wide row.  (The
    beam step's own writes, one token a step into [.., max_dec_steps +
    1] histories and pool pages, are scatters too and stay.)"""
    return [f"{name} = {shape} scatter(" for name, shape, opcode, _
            in _instructions(text)
            if opcode == "scatter" and max(_dims(shape), default=0)
            >= at_least]


def _over_wide_operand(instrs: list, width: int):
    """(name, shape, opcode, rest, operand shapes) of the instructions
    whose result or an operand has a dimension of ``width``.  An
    operand is printed by name alone, so its shape is looked up where
    it is defined."""
    wide = re.compile(rf"\[(?:\d+,)*{width}(?:,\d+)*\]")
    shape_of = {name: shape for name, shape, _, _ in instrs}
    for name, shape, opcode, rest in instrs:
        operands = re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0])
        shapes = [shape_of.get(o, "") for o in operands]
        if any(wide.search(s) for s in [shape] + shapes):
            yield name, shape, opcode, rest, [
                s for s in shapes if wide.search(s)]


def wide_row_orderings(text: str, width: int) -> list:
    """The instructions of ``compiled.as_text()`` that sort, or take a
    top-k of, an operand with a dimension of ``width`` (a sort's or a
    TopK custom call's result is checked too)."""
    return [f"{name} = {shape} {opcode}({rest}"[:200]
            for name, shape, opcode, rest, _ in _over_wide_operand(
                _instructions(text), width)
            if opcode in ("sort", "topk") or (
                opcode == "custom-call"
                and re.search(r'custom_call_target="[^"]*TopK', rest))]


def wide_reduces(text: str, width: int) -> list:
    """The reduces of ``compiled.as_text()`` that carry an index along
    (a variadic reduce whose result has an integer part) over an
    operand with a dimension of exactly ``width``: with the
    vocabulary's width, the passes of the selection (ops/topk.py), each
    one read of the row.  A row's maximum and its sum reduce one
    floating operand and are not among them."""
    return [f"{name} = {shape} reduce({rest}"[:200]
            for name, shape, opcode, rest, wide in _over_wide_operand(
                _instructions(text), width)
            if opcode == "reduce" and wide
            and re.search(r"\b[su]\d+\[", shape)]


def wide_gathers(text: str, width: int) -> list:
    """The gathers of ``compiled.as_text()`` that read an operand with
    a dimension of exactly ``width``, each as (instruction, the
    operand's dimensions): with the vocabulary's width, a lookup by
    word id.  Whose rows they are is in the other dimensions: a
    parameter matrix's hidden width, or the [slots, beam] of a step's
    score block.  (The operand is printed by name, so its shape is
    looked up where it is defined.)"""
    instrs = _instructions(text)
    shape_of = {name: shape for name, shape, _, _ in instrs}
    out = []
    for name, shape, opcode, rest in instrs:
        if opcode != "gather":
            continue
        operand = re.match(r"\s*%?([\w.\-]+)", rest)
        dims = _dims(shape_of.get(operand.group(1), "")) if operand else []
        if width in dims:
            out.append((f"{name} = {shape} gather({rest}"[:200], dims))
    return out


def score_gathers(text: str, vocab: int, rows: int) -> list:
    """``wide_gathers`` whose operand is a step's score block, ``rows``
    (slots x beam) rows of ``vocab`` scores, flattened or not — and not
    a parameter matrix (an embedding [V, emb_dim], a projection [V, H]
    or [H, V], its bias [V]): choose shapes where ``rows`` is none of
    those other widths."""
    return [g for g, dims in wide_gathers(text, vocab)
            if math.prod(dims) == rows * vocab]
