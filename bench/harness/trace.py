"""Reduction of a JAX profiler trace to device busy time, op times and gaps.

Read from the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX (``jax.profiler.ProfileData``). On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per executed HLO
op, named by the op's HLO text (``%fcf_grad.10 = f32[...] custom-call(...)``);
a control-flow op (``while``) spans its children. The line ``XLA Modules``
holds one event per program run. Host threads are lines of ``/host:CPU``;
the harness marks its own phases there with ``TraceAnnotation``s named
``bench.*`` and the traced window with ``bench.window``.

  busy      union of the intervals of the leaf ops (ops that contain no
            other op) inside the window, averaged over the chips used
  ops       device seconds and call count per op name (numeric suffix off)
            and first result shape, e.g. ``fusion f32[176300]``
  gaps      every interval of the window in which no leaf op runs, named by
            what it lies in: a program run (``in <module>``) or, outside
            any, the innermost host event over its midpoint (``host: ...``)
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

WINDOW_ANNOTATION = "bench.window"
_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s|$|=)")
_RESULT = re.compile(r"\(?([a-z]+\d*)\[([\d,]*)\]")


class Op(NamedTuple):
    name: str          # HLO op name without its numeric suffix
    text: str          # the event's full HLO text
    start: float       # seconds on the trace clock
    end: float


class TraceSummary(NamedTuple):
    window_s: float
    busy_s: float                               # averaged over the chips
    ops: Dict[str, Tuple[int, float]]           # key -> (calls, seconds)
    leaves: List[Op]                            # every leaf op, all chips
    gaps: Dict[str, float]                      # what idled the chip -> s
    chips: int

    @property
    def kernel_calls(self) -> List[Op]:
        """Every Pallas kernel call (``tpu_custom_call``)."""
        return [o for o in self.leaves if "tpu_custom_call" in o.text]

    def top_ops(self, n: int = 10) -> List[list]:
        items = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:n]
        return [[name, secs] for name, (_, secs) in items]

    def top_gaps(self, n: int = 10) -> List[list]:
        items = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:n]
        return [[name, secs] for name, secs in items]


def op_name(text: str) -> str:
    m = _OP_NAME.match(text.strip())
    return m.group(1) if m else text.split(" ", 1)[0].lstrip("%")


def result_shape(op: Op) -> Tuple[str, List[int]]:
    """Element type and dimensions of the op's first result, from its HLO
    text: ``("f32", [100, 1763])``; ``("", [])`` where the text has none."""
    m = _RESULT.match(op.text.split(" = ", 1)[-1])
    if not m:
        return "", []
    return m.group(1), [int(d) for d in m.group(2).split(",") if d]


def op_key(op: Op) -> str:
    """The op's name and its first result shape: ``fusion f32[176300]``."""
    dtype, dims = result_shape(op)
    if not dtype:
        return op.name
    return f"{op.name} {dtype}[{','.join(map(str, dims))}]"


def _events(line) -> Iterable[Tuple[str, float, float]]:
    for e in line.events:
        start = e.start_ns * 1e-9
        yield e.name, start, start + e.duration_ns * 1e-9


def _leaves(ops: List[Op]) -> List[Op]:
    """Ops that contain no other op (children of a while are leaves)."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt.start < o.end and nxt.end <= o.end \
                and (nxt.end - nxt.start) < (o.end - o.start):
            continue
        out.append(o)
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(iv: List[Tuple[float, float]], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _innermost(spans: List[Tuple[float, float, str]], t: float
               ) -> Optional[str]:
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return None if best is None else best[1]


def reduce_profile(pd, chips: int = 1) -> TraceSummary:
    """Summarize a ``jax.profiler.ProfileData`` over its ``bench.window``."""
    host = pd.find_plane_with_name("/host:CPU")
    host_spans: List[Tuple[float, float, str]] = []
    window: Optional[Tuple[float, float]] = None
    if host is not None:
        for line in host.lines:
            for name, s, e in _events(line):
                if name == WINDOW_ANNOTATION:
                    window = (s, e)
                elif e > s:
                    host_spans.append((s, e, name))
    planes = [pd.find_plane_with_name(f"/device:TPU:{i}")
              for i in range(chips)]
    planes = [p for p in planes if p is not None]
    if not planes:
        raise ValueError("the trace holds no /device:TPU plane")

    per_chip_ops: List[List[Op]] = []
    modules: List[Tuple[float, float, str]] = []
    for plane in planes:
        chip_ops = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                chip_ops += [Op(op_name(n), n, s, e)
                             for n, s, e in _events(line)]
            elif line.name == "XLA Modules":
                modules += [(s, e, n.split("(")[0])
                            for n, s, e in _events(line)]
        per_chip_ops.append(chip_ops)
    if window is None:
        starts = [o.start for ops in per_chip_ops for o in ops]
        ends = [o.end for ops in per_chip_ops for o in ops]
        if not starts:
            raise ValueError("the trace holds no device op")
        window = (min(starts), max(ends))
    lo, hi = window

    ops: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    all_leaves: List[Op] = []
    busy_total = 0.0
    gaps: Dict[str, float] = defaultdict(float)
    for chip_ops in per_chip_ops:
        inside = [o for o in chip_ops if o.end > lo and o.start < hi]
        leaves = _leaves(inside)
        all_leaves += leaves
        for o in leaves:
            acc = ops[op_key(o)]
            acc[0] += 1
            acc[1] += min(o.end, hi) - max(o.start, lo)
        busy = _clip(_union([(o.start, o.end) for o in leaves]), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = 0.5 * (s + e)
            mod = _innermost(modules, mid)
            if mod is not None:
                label = f"in {mod}"
            else:
                label = "host: " + (_innermost(host_spans, mid) or "untraced")
            gaps[label] += (e - s) / len(per_chip_ops)
    n = len(per_chip_ops)
    return TraceSummary(
        window_s=hi - lo, busy_s=busy_total / n,
        ops={k: (int(v[0]), v[1] / n) for k, v in ops.items()},
        leaves=all_leaves, gaps=dict(gaps), chips=n)


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` that ``jax.profiler`` wrote under the
    directory."""
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return str(found[-1])


def load(path: str, chips: int = 1) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), chips)


def kernel_seconds(summary: TraceSummary, names: Iterable[str]
                   ) -> Tuple[int, float]:
    """Calls and device seconds of the tpu_custom_calls with these names."""
    wanted = set(names)
    calls = [k for k in summary.kernel_calls if k.name in wanted]
    return len(calls), sum(k.end - k.start for k in calls)
