"""Post-SPMD HLO analysis: collective-byte accounting for the roofline.

``compiled.cost_analysis()`` reports FLOPs and bytes-accessed but NOT
collective traffic, so we parse the compiled HLO text and sum the result
sizes of every collective op. Methodology (documented in EXPERIMENTS.md):

  * all-gather / reduce-scatter / all-to-all / collective-permute move
    ~result_bytes per participating device (ring schedules move
    size*(g-1)/g ~= size), so we count 1x result bytes.
  * all-reduce moves ~2x result bytes per device (reduce-scatter +
    all-gather phases of a ring all-reduce).

The returned dict maps op kind -> bytes, plus "total" and a per-op list.
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# one HLO instruction result:  %name = TYPE[dims]{layout} op-name(...)
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\(")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one 'f32[128,1024]{1,0}' or tuple '(f32[2], bf16[4,4])'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


# computation header: a column-0 line "%name (args...) -> ... {" (args may
# nest parens, so match only the name prefix)
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")


def collective_bytes(hlo_text: str, while_trip: int = 1) -> Dict[str, int]:
    """Sum collective result bytes per op kind over a compiled HLO module.

    ``while_trip``: collectives inside while-loop *body* computations execute
    once per iteration, so they are weighted by the loop trip count (all
    whiles in our programs are layer scans with the same known trip count);
    top-level collectives — e.g. the stacked gradient all-reduce that the
    scan emits once, outside the loop — count once. Without this split a
    probe-based correction double-counts the gradient sync ~2x.
    """
    # split the module into computations; record collectives per computation
    per_comp: Dict[str, List[Tuple[str, int]]] = {}
    bodies: set = set()
    current = "__module__"
    for line in hlo_text.splitlines():
        mc = _COMP_RE.match(line)
        if mc:
            current = mc.group(1)
            continue
        for mb in _BODY_RE.finditer(line):
            bodies.add(mb.group(1))
        m = _INSTR_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        kind = kind.replace("-start", "")
        nbytes = _shape_bytes(shape_str)
        # all-gather-start result tuple repeats (operand, result); count once
        if "(" in shape_str and kind in ("all-gather", "collective-permute"):
            nbytes //= 2
        weight = 2 if kind == "all-reduce" else 1
        per_comp.setdefault(current, []).append((kind, weight * nbytes))

    out: Dict[str, int] = {k: 0 for k in COLLECTIVE_KINDS}
    n_ops = 0
    in_body = 0
    for comp, ops in per_comp.items():
        mult = while_trip if comp in bodies else 1
        for kind, nbytes in ops:
            out[kind] += mult * nbytes
            n_ops += 1
            if mult > 1:
                in_body += 1
    out["total"] = sum(out[k] for k in COLLECTIVE_KINDS)
    out["num_ops"] = n_ops
    out["num_in_loop"] = in_body
    return out


# Published per-chip peaks keyed by ``jax.Device.device_kind`` (Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s of interconnect over 4 links = 50 GB/s per link).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The :data:`PEAKS` entry for ``device_kind``; unknown kinds raise."""
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}")
    return dict(PEAKS[device_kind])


def roofline_terms(
    flops: float,
    bytes_accessed: float,
    coll_bytes: float,
    num_chips: int,
    *,
    peak_flops: float,               # FLOP/s per chip   (see peaks_for)
    hbm_bw: float,                   # bytes/s per chip
    link_bw: float,                  # bytes/s per ICI link
) -> Dict[str, float]:
    """The three roofline terms (seconds) + dominant bottleneck.

    ``flops``/``bytes_accessed`` are whole-program (cost_analysis on the
    SPMD module is per-device already on recent jax; we treat them as
    per-device and divide only by 1 -- callers pass per-device numbers).
    ``coll_bytes`` is per-device collective traffic from the HLO.
    """
    compute_s = flops / peak_flops
    memory_s = bytes_accessed / hbm_bw
    collective_s = coll_bytes / link_bw
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    terms["bottleneck"] = max(terms, key=lambda k: terms[k]).replace("_s", "")
    terms["step_time_s"] = max(compute_s, memory_s, collective_s)
    return terms
