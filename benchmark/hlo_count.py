"""Counts from a compiled step's HLO text, kept with the benchmark: the
bytes its collectives move.

The program's own ``obs.spmd.collective_profile`` reads only array-shaped
results; the compiler combines most gradients into a few all-reduces with
tuple results, which it leaves out (it reads 77 of 326 MB in cell
gpt2s_pretrain_1k_dp4, PR 23). So the benchmark counts for itself.
"""
import math
import re

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 0.5, "u4": 0.5}
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (?P<type>\(.*?\)|\S+) "
    r"(?P<op>" + "|".join(COLLECTIVES) + r")(?P<start>-start)?\(", re.M)
_ARRAY = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\]")


def array_bytes(type_text):
    """Bytes of every array in an HLO type (an array or a tuple of them);
    layouts in braces and ``/*index=n*/`` comments are skipped over."""
    type_text = re.sub(r"/\*.*?\*/", "", re.sub(r"\{[^{}]*\}", "", type_text))
    out = []
    for dtype, dims in _ARRAY.findall(type_text):
        if dtype not in DTYPE_BYTES:
            raise ValueError(f"unknown HLO element type {dtype!r}")
        out.append(DTYPE_BYTES[dtype] *
                   math.prod(int(d) for d in dims.split(",") if d))
    return out


def collective_bytes(hlo_text):
    """{kind: bytes a step moves through collectives of that kind}, by the
    size of each collective's result (a count: it repeats exactly). An
    asynchronous pair is counted once, at its ``-start``, whose result
    carries the operands beside the outputs: the larger half is taken."""
    out = {}
    for m in _INSTRUCTION.finditer(hlo_text):
        sizes = array_bytes(m.group("type"))
        moved = sum(sizes)
        if m.group("start") and len(sizes) > 1:
            half = len(sizes) // 2
            moved = max(sum(sizes[:half]), sum(sizes[half:]))
        out[m.group("op")] = out.get(m.group("op"), 0) + moved
    return out
