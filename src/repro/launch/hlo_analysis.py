"""HLO-text analysis for the roofline report.

``cost_analysis()`` gives FLOPs and bytes accessed, but NOT collective
traffic — we parse the optimized HLO and sum operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
op (per-device bytes, since SPMD HLO shapes are per-device).

:func:`memory_stats` / :func:`lowered_memory` read the backend's buffer
assignment off a compiled executable (``memory_analysis()``) — the
ground truth the memplan peak-bytes prediction is judged against.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# e.g.  %ag = bf16[8,128,256]{2,1,0} all-gather(...), or tuple shapes
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],{}\s]+?)\s+"
    r"(" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device bytes moved by each collective kind (output shapes;
    '-done' ops are skipped so async pairs are not double counted)."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        out[kind] += _shape_bytes(shape_str)
        out["count"] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def memory_stats(compiled: Any) -> Dict[str, int]:
    """Buffer-assignment sizes of a compiled executable.  ``temp_bytes``
    is the scratch the program needs beyond arguments/outputs — the
    number liveness planning moves; ``peak_bytes`` approximates total
    residency while a step runs.  Raises when the backend reports no
    analysis: a missing measurement must not read as zero bytes."""
    mem = compiled.memory_analysis()
    if mem is None:
        raise RuntimeError("the backend reported no memory analysis")

    def _get(name: str) -> int:
        return int(getattr(mem, name, 0) or 0)

    temp = _get("temp_size_in_bytes")
    args = _get("argument_size_in_bytes")
    outs = _get("output_size_in_bytes")
    return {
        "temp_bytes": temp,
        "argument_bytes": args,
        "output_bytes": outs,
        "generated_code_bytes": _get("generated_code_size_in_bytes"),
        "peak_bytes": temp + args + outs,
    }


def lowered_memory(fn: Any, *args: Any) -> Dict[str, int]:
    """AOT-lower ``fn`` (a jax.jit callable) at ``args`` (concrete
    arrays or ShapeDtypeStructs), compile, and return
    :func:`memory_stats` — one explicit compile, separate from any
    call-path jit cache.  Compile errors propagate."""
    return memory_stats(fn.lower(*args).compile())


def op_histogram(hlo_text: str, top: int = 15) -> List[Tuple[str, int]]:
    """Count HLO opcodes — used to spot remat recompute / layout churn."""
    counts: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = re.match(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?:\([^)]*\)|[\w\[\],{}\s]+?)\s+([\w\-]+)\(", line)
        if m:
            counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return sorted(counts.items(), key=lambda kv: -kv[1])[:top]
