"""Optimised HLO text taken apart: the one parser.

`compiled.as_text()` of a jitted program lists, computation by
computation, every instruction of the executable under the name the
device trace shows it by (`fusion.1290`, `copy.1534`: both are the one
executable), with its result shape, its opcode and, in `metadata={...}`,
the `op_name` JAX gave the operation it came from (the name stack, a
`jax.named_scope` included) and where in the source that was. What reads
a program by its text goes through here: `obs/phases.py` (which phase an
instruction belongs to) and `tools/build_program_copies.py` (which
arrays a program copies whole).

Nothing here imports jax: text in, tuples out.
"""
from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Tuple

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}
SHAPE = re.compile(r"^([a-z]+[0-9]*)\[([0-9,]*)\]")
COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
CALLEES = ("body", "condition", "true_computation", "false_computation",
           "branch_computations", "calls")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*.*?\*/|\s+")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SOURCE = re.compile(r'source_file="([^"]*)"(?: source_line=(\d+))?')
_FRAME = re.compile(r"stack_frame_id=(\d+)")
_NAME = re.compile(r"%([\w.\-]+)")
_HEADER_ROW = re.compile(r"^(\d+) (.*)$")


class Instr(NamedTuple):
    computation: str    # the computation the instruction sits in
    name: str           # without the leading %
    shape: str          # as written, layout and all
    opcode: str
    rest: str           # operands, attributes and metadata


def instruction(line: str):
    """(name, shape, opcode, the rest) of one line of HLO text, or None.
    A tuple's shape has spaces and comments in it, so no single pattern
    takes the line apart."""
    left, eq, right = line.strip().partition(" = ")
    if not eq:
        return None
    name = left.split()[-1].lstrip("%")
    if right.startswith("("):       # a tuple shape: to its closing bracket
        depth = 0
        for at, ch in enumerate(right):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, right = right[:at + 1], right[at + 1:].lstrip()
    else:
        shape, _, right = right.partition(" ")
    opcode, bracket, rest = right.partition("(")
    return (name, shape, opcode, rest) if bracket else None


def plain_shape(shape: str) -> str:
    """A result shape without layouts, comments and spaces:
    `f32[11043840]{0:T(1024)}` -> `f32[11043840]`. The text of an
    executable and a trace event of it agree on this much whatever
    either prints of the layout."""
    return _LAYOUT.sub("", shape)


def nbytes(shape_text: str) -> int:
    found = SHAPE.match(shape_text)
    if not found or found.group(1) not in DTYPE_BYTES:
        return 0
    n = 1
    for d in found.group(2).split(","):
        n *= int(d) if d else 1
    return DTYPE_BYTES[found.group(1)] * n


def instructions(hlo: str) -> List[Instr]:
    """Every instruction of every computation, in the text's order."""
    where, out = None, []
    for line in hlo.splitlines():
        head = COMPUTATION.match(line)
        if head:
            where = head.group(2)
            continue
        found = instruction(line)
        if found and where:
            out.append(Instr(where, *found))
    return out


def callees(instr: Instr) -> List[Tuple[str, str]]:
    """[(attribute, computation)] of the computations an instruction
    names: a while's body, a conditional's branches, a fusion's inside."""
    out = []
    for key in CALLEES:
        for group in re.findall(
                key + r"=\{?(%?[\w.\-]+(?:, %[\w.\-]+)*)", instr.rest):
            out += [(key, c) for c in group.replace("%", "").split(", ")]
    return out


_TO_APPLY = re.compile(r"to_apply=%?([\w.\-]+)")


def inner_computations(instrs: List[Instr]) -> set:
    """The computations that never execute on their own: the inside of a
    fusion, and what a reduce, sort, scatter or the like applies per
    element. A trace shows the instruction that names them, not theirs."""
    inside = set()
    for ins in instrs:
        if ins.opcode == "fusion":
            inside.update(c for k, c in callees(ins) if k == "calls")
        if ins.opcode != "call":
            inside.update(_TO_APPLY.findall(ins.rest))
    return inside


def roles(instrs: List[Instr], loop_of=()) -> Dict[str, Tuple[str, str]]:
    """{computation: (what it is to its caller, the caller's computation)}.
    A `body` among `loop_of` (computations) is marked the round loop."""
    role = {}
    for ins in instrs:
        for key, callee in callees(ins):
            what = f"{key} of {ins.opcode} {ins.name}"
            if key == "body" and callee in loop_of:
                what += " (the round loop)"
            role[callee] = (what, ins.computation)
    return role


def place(role: Dict[str, Tuple[str, str]], comp: str) -> str:
    """The way down to `comp` from the entry computation."""
    steps = []
    while comp in role and len(steps) < 16:
        what, comp = role[comp]
        steps.append(what)
    return " in ".join(steps + ["entry"])


def frames(hlo: str) -> Dict[int, Tuple[str, int]]:
    """{stack_frame_id: (file, line)} from the text's header tables
    (`FileNames`, `FileLocations`, `StackFrames`): where jax 0.9 keeps
    what older texts wrote into each instruction's metadata. A frame is
    the innermost call site; empty where the text has no such tables."""
    tables, at = {}, None
    for line in hlo.splitlines():
        line = line.strip()
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            at = tables.setdefault(line, {})
        elif line.startswith(("%", "ENTRY", "ROOT")):
            break           # past the header
        elif at is not None and _HEADER_ROW.match(line):
            row = _HEADER_ROW.match(line)
            at[int(row.group(1))] = row.group(2)
    files = {k: v.strip('"') for k, v in tables.get("FileNames", {}).items()}
    out = {}
    for fid, text in tables.get("StackFrames", {}).items():
        loc = re.search(r"file_location_id=(\d+)", text)
        where = tables.get("FileLocations", {}).get(
            int(loc.group(1))) if loc else None
        if not where:
            continue
        name = re.search(r"file_name_id=(\d+)", where)
        line_no = re.search(r"\bline=(\d+)", where)
        if name and line_no:
            out[fid] = (files.get(int(name.group(1)), ""),
                        int(line_no.group(1)))
    return out


def origin(instr: Instr, frame_table=None):
    """(op_name, source file, source line) of an instruction's metadata;
    None for each the text does not give."""
    name = _OP_NAME.search(instr.rest)
    src = _SOURCE.search(instr.rest)
    if src:
        file_, line_no = src.group(1), int(src.group(2) or 0) or None
    else:
        frame = _FRAME.search(instr.rest)
        file_, line_no = (frame_table or {}).get(
            int(frame.group(1)), (None, None)) if frame else (None, None)
    return (name.group(1) if name else None), file_, line_no


def is_kernel(instr: Instr) -> bool:
    """A Mosaic (Pallas) kernel: one custom call, however many grid
    steps it runs."""
    return instr.opcode == "custom-call" and KERNEL_TARGET in instr.rest


def operands(instr: Instr) -> List[str]:
    """Names of the instructions an instruction reads, in order."""
    depth = 1
    for at, ch in enumerate(instr.rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return _NAME.findall(instr.rest[:at])
    return _NAME.findall(instr.rest)
