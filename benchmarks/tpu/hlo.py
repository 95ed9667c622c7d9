"""The Pallas kernels of a compiled program, read from its HLO text: for
each ``tpu_custom_call`` instruction its name (what the device trace calls
the op), the jitted function that makes the ``pallas_call`` (from the
instruction's ``op_name`` metadata, e.g. ``jit(clip_accum_inplace)``) and
the shapes and element sizes of its operands (from
``operand_layout_constraints``) and results."""
from __future__ import annotations

import re

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
            "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8}
_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+custom-call\(")
_CALLER = re.compile(r'op_name="[^"]*jit\(([\w.]+)\)/pallas_call')
_LAYOUTS = re.compile(r"operand_layout_constraints=\{(.*?)\}, [a-z_]+=")


def _arrays(text: str):
    out = []
    for dtype, dims in _ARRAY.findall(text):
        if dtype in ITEMSIZE:
            shape = tuple(int(d) for d in dims.split(",") if d)
            out.append((shape, ITEMSIZE[dtype]))
    return out


def custom_calls(text: str) -> dict:
    """op name -> {"caller", "operands", "results"} for every Pallas
    call."""
    out = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        caller = _CALLER.search(line)
        layouts = _LAYOUTS.search(line)
        out[m.group(1)] = {
            "caller": caller.group(1) if caller else None,
            "results": _arrays(m.group(2)),
            "operands": _arrays(layouts.group(1)) if layouts else []}
    return out
