"""Counts read from a compiled program's HLO text.

A copy of ``repro.launch.dryrun.collective_bytes``, kept with the benchmark so
that no change to the program can change how its collectives are counted.
"""
from __future__ import annotations

import re

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_LINE_RE = re.compile(
    r"=\s+(.*?)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)([\w\-.]*)\(")


def _shapes_bytes(shape_str: str) -> int:
    """Total bytes of all HLO shapes in a string like '(f32[8,128]{1,0}, u32[])'."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Result-shape bytes of every collective in a compiled (per-device)
    program, by kind, with ``total`` and per-kind ``counts``.  Async pairs
    count at their ``-start``."""
    out = {k: 0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _COLL_LINE_RE.search(line)
        if m is None:
            continue
        shape_str, op, suffix = m.groups()
        if "done" in suffix:
            continue
        out[op] += _shapes_bytes(shape_str)
        counts[op] += 1
    out["total"] = sum(out[k] for k in COLLECTIVES)
    out["counts"] = counts
    return out
