"""HLO-text analysis: collective bytes + op census for the roofline.

``cost_analysis()`` has no collective term, so we parse the compiled (or
lowered) HLO and sum the *result* sizes of every collective op. Methodology
notes (EXPERIMENTS.md §Roofline):
  - all-gather/all-to-all/collective-permute: result bytes ~= bytes moved
    through ICI per device (all-gather result includes the local shard, so
    this slightly overcounts by 1/n).
  - all-reduce: ring moves ~2x the buffer; we count 2x result bytes.
  - reduce-scatter: result is the reduced shard; bytes moved ~= input shard
    size * (n-1)/n ~= result bytes * 1.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  bf16[16,128,2048]{2,1,0} all-gather(...)
_OP_RE = re.compile(
    r"=\s*([a-z0-9]+)\[([\d,]*)\][^=]*?\b(" +
    "|".join(_COLLECTIVES) + r")\(")
# tuple-result form: (bf16[...], bf16[...]) all-reduce(
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s*(" + "|".join(_COLLECTIVES) + r")\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a dict (empty when XLA reports
    nothing)."""
    return compiled.cost_analysis() or {}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum per-collective-kind bytes (per device) from HLO text."""
    out: Dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _TUPLE_RE.search(line)
        if m:
            shapes, kind = m.groups()
            b = sum(_shape_bytes(dt, dm)
                    for dt, dm in _SHAPE_RE.findall(shapes))
            out[kind] += 2 * b if kind == "all-reduce" else b
            continue
        m = _OP_RE.search(line)
        if m:
            dtype, dims, kind = m.groups()
            b = _shape_bytes(dtype, dims)
            out[kind] += 2 * b if kind == "all-reduce" else b
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return dict(out)


def _line_collective(line: str):
    """(kind, bytes) for a collective op on this line, else None."""
    m = _TUPLE_RE.search(line)
    if m:
        shapes, kind = m.groups()
        b = sum(_shape_bytes(dt, dm) for dt, dm in _SHAPE_RE.findall(shapes))
        return kind, (2 * b if kind == "all-reduce" else b)
    m = _OP_RE.search(line)
    if m:
        dtype, dims, kind = m.groups()
        b = _shape_bytes(dtype, dims)
        return kind, (2 * b if kind == "all-reduce" else b)
    return None


_BLOCK_RE = re.compile(r"^(ENTRY )?%?([\w.\-]+)\s*\(.*\{\s*$")
_WHILE_RE = re.compile(r"condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_CONST_RE = re.compile(r"s32\[\] constant\((\d+)\)")


def loop_aware_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Collective bytes with while-loop trip-count multiplication.

    XLA HLO places each while body/condition in its own named computation;
    collectives inside a scanned layer stack execute trip-count times but
    appear once in the text. This walks the computation graph: bytes(block) =
    local collectives + sum over whiles of trips * bytes(body), with trips
    read from the loop condition's s32 constant (upper bound if several).
    """
    blocks: Dict[str, list] = {}
    entry = None
    cur = None
    for ln in hlo_text.splitlines():
        m = _BLOCK_RE.match(ln)
        if m:
            cur = m.group(2)
            blocks[cur] = []
            if m.group(1):
                entry = cur
            continue
        if ln.startswith("}"):
            cur = None
            continue
        if cur is not None:
            blocks[cur].append(ln)

    def trips(cond_name: str) -> int:
        vals = [int(v) for ln in blocks.get(cond_name, [])
                for v in _CONST_RE.findall(ln)]
        return max(vals) if vals else 1

    memo: Dict[str, Dict[str, int]] = {}

    def total(name: str) -> Dict[str, int]:
        if name in memo:
            return memo[name]
        memo[name] = {}        # cycle guard
        acc: Dict[str, int] = defaultdict(int)
        for ln in blocks.get(name, []):
            lc = _line_collective(ln)
            if lc:
                acc[lc[0]] += lc[1]
            wm = _WHILE_RE.search(ln)
            if wm and " while(" in ln:
                t = trips(wm.group(1))
                for k, v in total(wm.group(2)).items():
                    acc[k] += t * v
        memo[name] = dict(acc)
        return memo[name]

    if entry is None:
        return collective_bytes(hlo_text)
    out = total(entry)
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def op_census(hlo_text: str, ops=("fusion", "dot", "convolution",
                                  "reshape", "transpose", "copy")) -> dict:
    """Rough op frequency census — remat/redundancy smell test."""
    counts = defaultdict(int)
    for line in hlo_text.splitlines():
        s = line.strip()
        for op in ops + _COLLECTIVES:
            if re.search(rf"= [a-z0-9\[\]{{}},.]* ?{op}\(", s) or \
               re.search(rf"\b{op}\(", s.split("=")[-1][:40]):
                counts[op] += 1
                break
    return dict(counts)
