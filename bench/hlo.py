"""Classify the instructions of a compiled program from its HLO text.

The device trace names each event by the HLO instruction it ran (and the
module it belongs to), not by the Python code that made it.  This map,
built from ``compiled.as_text()`` of the same program in the same process,
says what each instruction is:

- ``collective``: an all-reduce, reduce-scatter, all-gather,
  collective-permute or all-to-all (and their ``-start``/``-done``
  halves), a fusion or async wrapper around one, or a Pallas kernel that
  communicates (``has_communication`` in its Mosaic config);
- ``kernel``: any other ``tpu_custom_call``; its kernel is the name of the
  Pallas kernel function found in the serialized Mosaic body;
- ``other``: everything else.
"""
from __future__ import annotations

import base64
import dataclasses
import re

COLLECTIVE_OPCODES = frozenset(
    base + suffix
    for base in ("all-reduce", "reduce-scatter", "all-gather",
                 "collective-permute", "all-to-all")
    for suffix in ("", "-start", "-done"))

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(?P<name>[^\s=]+) = (?P<rest>.*)$")
_COMP = re.compile(r"^(?:ENTRY\s+)?%(?P<name>[^\s(]+)\s.*\{\s*$")
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_KERNEL = re.compile(rb"[A-Za-z_][A-Za-z0-9_]*_kernel\b")
DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "s8": 1, "u8": 1,
               "s32": 4, "u32": 4, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1,
               "s4": 1, "s16": 2, "s64": 8}


@dataclasses.dataclass(frozen=True)
class Instr:
    opcode: str
    kind: str                       # collective | kernel | other
    kernel: str | None              # Pallas kernel function, if any
    shapes: tuple                   # result array shapes: ((dtype, dims), ...)
    operands: tuple                 # operand shapes where the HLO states them
    op_name: str                    # metadata op_name ("" if none)

    @property
    def label(self) -> str:
        """The name the breakdown gives this instruction's time."""
        if self.kernel:
            return self.kernel
        if self.kind == "collective":
            return self.opcode
        tail = self.op_name.rsplit("/", 1)[-1] if self.op_name else self.opcode
        return ("bwd:" if "transpose(" in self.op_name else "") + tail


def _split_shape(rest: str) -> tuple[str, str]:
    """``rest`` after ``name = ``: (result shape text, remainder)."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[:i + 1], rest[i + 1:].lstrip()
    head, _, tail = rest.partition(" ")
    return head, tail


def _shapes(text: str) -> tuple:
    return tuple((dt, tuple(int(x) for x in dims.split(",") if x))
                 for dt, dims in _SHAPE.findall(text))


def _braced(text: str, key: str) -> str:
    """The balanced ``{...}`` that follows ``key`` in ``text`` ("" if none)."""
    start = text.find(key + "{")
    if start < 0:
        return ""
    depth = 0
    for i in range(start + len(key), len(text)):
        depth += text[i] == "{"
        depth -= text[i] == "}"
        if depth == 0:
            return text[start + len(key) + 1:i]
    return ""


def _kernel_of(line: str) -> str | None:
    m = re.search(r'"body":"([^"]*)"', line)
    if not m:
        return None
    try:
        raw = base64.b64decode(m.group(1))
    except ValueError:
        return None
    names = [n.decode() for n in _KERNEL.findall(raw)]
    return names[0] if names else None


def _logical_lines(text: str):
    """Lines of the HLO text, with an instruction whose braces are still
    open at the end of a line (a multi-line attribute) joined to the lines
    that close them."""
    pending, depth = None, 0
    for line in text.splitlines():
        if pending is not None:
            pending += " " + line.strip()
            depth += line.count("{") - line.count("}")
            if depth <= 0:
                yield pending
                pending = None
            continue
        if _INSTR.match(line):
            depth = line.count("{") - line.count("}")
            if depth > 0:
                pending = line
                continue
        yield line
    if pending is not None:
        yield pending


def parse(text: str) -> tuple[str, dict[str, Instr]]:
    """(module name, {instruction name: Instr}) of one compiled program."""
    module = ""
    raw: dict[str, tuple] = {}          # name -> (opcode, shape, tail, line)
    comp_ops: dict[str, set] = {}       # computation -> its opcodes
    current = None
    for line in _logical_lines(text):
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c:
                current = c.group("name")
                comp_ops.setdefault(current, set())
            continue
        shape, tail = _split_shape(m.group("rest"))
        opcode = tail.split("(", 1)[0].strip()
        raw[m.group("name")] = (opcode, shape, tail, line)
        if current is not None:
            comp_ops[current].add(opcode)
    out = {}
    for name, (opcode, shape, tail, line) in raw.items():
        called = re.findall(r"(?:calls|to_apply)=%([^\s,}]+)", tail)
        wraps_collective = any(comp_ops.get(c, set()) & COLLECTIVE_OPCODES
                               for c in called)
        kernel = None
        kind = "other"
        if 'custom_call_target="tpu_custom_call"' in tail:
            kernel = _kernel_of(line)
            kind = ("collective" if '"has_communication":true' in tail
                    else "kernel")
        elif opcode in COLLECTIVE_OPCODES or (
                wraps_collective and opcode in ("fusion", "async-start",
                                                "async-update", "async-done")):
            kind = "collective"
        constraints = _braced(tail, "operand_layout_constraints=")
        op_name = re.search(r'op_name="([^"]*)"', tail)
        out[name] = Instr(opcode=opcode, kind=kind, kernel=kernel,
                          shapes=_shapes(shape),
                          operands=_shapes(constraints),
                          op_name=op_name.group(1) if op_name else "")
    return module, out
