"""Read an operation's shapes out of the name the TPU profiler gives it.

On the TPU each event of the device's "XLA Ops" line is named by its HLO
instruction, e.g.::

    %swiglu_ffn.4 = bf16[4,2560]{1,0:T(4,128)(2,1)S(1)} custom-call(
        bf16[4,2560]{...S(1)} %fusion.90, bf16[2560,9728]{...} %w, ...)

so the operands' shapes, dtypes and memory spaces (``S(1)``: the
compiler placed the buffer in on-chip vector memory, VMEM) come with the
event.  A kernel's operations and bytes are computed from these shapes.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass

_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|f16|bf16|f32|f64|"
                    r"f8e4m3fn|f8e5m2|s4|u4)\[([\d,]*)\](\{[^}]*\})?")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4,
             "u32": 4, "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4,
             "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 0.5, "u4": 0.5}


@dataclass(frozen=True)
class Buffer:
    dtype: str
    dims: tuple
    vmem: bool           # memory space 1 (S(1)) on the TPU

    @property
    def nbytes(self) -> float:
        return math.prod(self.dims) * _ITEMSIZE[self.dtype]


@dataclass(frozen=True)
class Op:
    name: str            # "swiglu_ffn" (the instruction's name, no suffix)
    opcode: str          # "custom-call", "fusion", "all-reduce", ...
    outputs: tuple       # Buffers
    operands: tuple      # Buffers


def base_name(text: str) -> str:
    """``"%decode_attention.4 = ..."`` -> ``"decode_attention"``;
    ``"jit_decode(1234)"`` -> ``"jit_decode"``."""
    head = text.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]
    return re.sub(r"\.\d+$", "", head)


def _buffers(text: str) -> tuple:
    out = []
    for dtype, dims, layout in _SHAPE.findall(text):
        d = tuple(int(x) for x in dims.split(",") if x)
        out.append(Buffer(dtype, d, "S(1)" in (layout or "")))
    return tuple(out)


def parse(text: str) -> Op:
    """Split one HLO instruction into its name, opcode, outputs and
    operands.  Operands are the shapes inside the opcode's parentheses."""
    name = base_name(text)
    rhs = text.split(" = ", 1)[1] if " = " in text else ""
    # the output is a shape or a tuple of shapes; the opcode follows it
    depth, i = 0, 0
    while i < len(rhs):
        c = rhs[i]
        if c in "({[":
            depth += 1
        elif c in ")}]":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    out_text, rest = rhs[:i], rhs[i + 1:]
    m = re.match(r"([\w\-]+)\(", rest)
    opcode = m.group(1) if m else ""
    args = ""
    if m:
        depth, j = 1, m.end()
        while j < len(rest) and depth:
            depth += {"(": 1, ")": -1}.get(rest[j], 0)
            j += 1
        args = rest[m.end():j - 1]
    return Op(name, opcode, _buffers(out_text), _buffers(args))
