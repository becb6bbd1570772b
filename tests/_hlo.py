"""Reading a compiled program's text in tests (no test lives here)."""

import re

_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")


def wide_row_orderings(text: str, width: int) -> list:
    """The instructions of ``compiled.as_text()`` that sort, or take a
    top-k of, an operand with a dimension of ``width``.  An operand is
    printed by name alone, so its shape is looked up where it is
    defined; a sort's or a TopK custom call's result is checked too."""
    wide = re.compile(rf"\[(?:\d+,)*{width}(?:,\d+)*\]")
    instrs = [m.groups() for m in map(_INSTR.match, text.splitlines()) if m]
    shape_of = {name: shape for name, shape, _, _ in instrs}
    bad = []
    for name, shape, opcode, rest in instrs:
        if not (opcode in ("sort", "topk") or (
                opcode == "custom-call"
                and re.search(r'custom_call_target="[^"]*TopK', rest))):
            continue
        operands = re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0])
        shapes = [shape] + [shape_of.get(o, "") for o in operands]
        if any(wide.search(s) for s in shapes):
            bad.append(f"{name} = {shape} {opcode}({rest}"[:200])
    return bad
