"""What the benchmark reads from the text of a compiled step.

A Pallas kernel is a `custom-call` whose target is `tpu_custom_call`. Its
HLO instruction name is what the device trace shows; the kernel's own name
(`pallas_call(name=)`) is the path component before `/pallas_call` in the
instruction's `op_name` metadata, possibly wrapped (`jvp(add_ln_fwd)`,
`transpose(jvp(add_ln_bwd))`). Where XLA fuses the call with a neighbour
(the scan's stash update), the trace shows the `fusion` instruction that
`calls=` the computation holding it, so that name leads to the call too.

Shapes come with their layout, and a layout says where the buffer lives:
`S(1)` is the chip's on-chip memory, which XLA's memory-space assignment
gives to buffers that fit (on the v5e, whole [32768, 768] bf16 activations);
no `S(n)` is HBM. Result shapes are left of `custom-call(`; an operand's is
that of the instruction that produces it.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_MOSAIC = 'custom_call_target="tpu_custom_call"'
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=\s*(.*)$")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([A-Za-z0-9_.\-]+)\s*\(.*\{\s*$")
_SHAPE = re.compile(
    r"\b(pred|[a-z]+[0-9]+(?:e[0-9]m[0-9][a-z]*)?)\[([0-9,]*)\](\{[^}]*\})?")
_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")
_SPACE = re.compile(r"S\((\d+)\)")
_CONSTRAINTS = re.compile(r"operand_layout_constraints=\{(.*?)\}, [a-z_]+=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([A-Za-z0-9_.\-]+)")
_OPERAND = re.compile(r"%([A-Za-z0-9_.\-]+)")
_BITS = re.compile(r"[a-z]+([0-9]+)")


@dataclasses.dataclass(frozen=True)
class Shape:
    dtype: str
    dims: Tuple[int, ...]
    space: int = 0  # 0: HBM; 1: on-chip memory (layout `S(1)`)

    @property
    def nbytes(self) -> float:
        n = 1
        for d in self.dims:
            n *= d
        return n * dtype_bytes(self.dtype)


def dtype_bytes(dtype: str) -> float:
    if dtype == "pred":
        return 1.0
    m = _BITS.match(dtype)
    if not m:
        raise ValueError(f"unknown HLO element type {dtype!r}")
    return int(m.group(1)) / 8.0


def shapes_in(text: str) -> List[Shape]:
    out = []
    for dtype, dims, layout in _SHAPE.findall(text):
        space = _SPACE.search(layout or "")
        out.append(Shape(dtype, tuple(int(x) for x in dims.split(",") if x),
                         int(space.group(1)) if space else 0))
    return out


@dataclasses.dataclass(frozen=True)
class MosaicCall:
    instruction: str
    kernel: str
    operands: Tuple[Shape, ...]
    results: Tuple[Shape, ...]

    @property
    def bytes_moved(self) -> float:
        """Every operand read once and every result written once."""
        return sum(s.nbytes for s in self.operands + self.results)

    @property
    def hbm_bytes(self) -> float:
        """The same, over the buffers the compiled step keeps in HBM: the
        least traffic the call can have with HBM as XLA placed it."""
        return sum(s.nbytes for s in self.operands + self.results
                   if s.space == 0)


def kernel_name(op_name: str) -> str:
    parts = op_name.split("/")
    if "pallas_call" not in parts:
        return ""
    before = parts[parts.index("pallas_call") - 1]
    words = re.findall(r"[A-Za-z0-9_]+", before)
    return words[-1] if words else ""


@dataclasses.dataclass
class StepText:
    """The instructions of a compiled step, as far as the benchmark reads
    them: Mosaic calls by the name the trace shows, and every
    instruction's `op_name` for the breakdown's labels."""
    calls: Dict[str, MosaicCall]
    op_names: Dict[str, str]

    @property
    def kernels(self) -> List[str]:
        return sorted({c.kernel for c in self.calls.values()})

    def kernel_of(self, instruction: str) -> Optional[str]:
        call = self.calls.get(instruction)
        return call.kernel if call else None

    def label(self, instruction: str) -> str:
        """`fusion.531` says little; `fusion.531 while/body/dot_general`
        says what the fusion's root came from."""
        kernel = self.kernel_of(instruction)
        if kernel:
            return kernel
        tail = [p for p in self.op_names.get(instruction, "").split("/")
                if p and not p.startswith("jit(")][-3:]
        return f"{instruction} {'/'.join(tail)}" if tail else instruction


def read_step(text: str) -> StepText:
    results: Dict[str, List[Shape]] = {}
    op_names: Dict[str, str] = {}
    mosaic: List[Tuple[str, str, str]] = []  # (instruction, computation, line)
    fusions: List[Tuple[str, str]] = []  # (instruction, called computation)
    computation = ""
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                computation = c.group(1)
            continue
        name, rest = m.groups()
        padded = " " + rest
        opcode = _OPCODE.search(padded)
        head = padded[:opcode.start()] if opcode else padded
        results[name] = shapes_in(head)
        op = _OP_NAME.search(line)
        if op:
            op_names[name] = op.group(1)
        if _MOSAIC in line:
            mosaic.append((name, computation, line))
        elif opcode and opcode.group(1) == "fusion":
            called = _CALLS.search(line)
            if called:
                fusions.append((name, called.group(1)))

    calls: Dict[str, MosaicCall] = {}
    inside: Dict[str, List[MosaicCall]] = {}
    for name, comp, line in mosaic:
        args = line.split(" custom-call(", 1)[1].split(
            "), custom_call_target", 1)[0]
        constraint = _CONSTRAINTS.search(line)
        seen = shapes_in(constraint.group(1)) if constraint else []
        operands = []
        for i, producer in enumerate(_OPERAND.findall(args)):
            made = results.get(producer, [])
            # an operand is its producer's one result; the kernel's own view
            # of it stands in where the producer is not an array
            operands.append(made[0] if len(made) == 1
                            else seen[i] if i < len(seen) else None)
        call = MosaicCall(
            instruction=name,
            kernel=kernel_name(op_names.get(name, "")),
            operands=tuple(s for s in operands if s is not None),
            results=tuple(results[name]))
        calls[name] = call
        inside.setdefault(comp, []).append(call)
    for name, called in fusions:
        held = inside.get(called, [])
        if len(held) == 1:
            calls[name] = held[0]
    return StepText(calls, op_names)
