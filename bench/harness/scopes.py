"""The program's own names in a training trace: the phase scopes its compiled
round carries on each device op, and the spans its loop opens on the host.

Device side. ``jax.named_scope`` puts the phase into each op's name stack
(``jit(scan_chunk)/while/body/closed_call/fl_gather/gather:``), which XLA
keeps in the ``tf_op`` stat of the op's event metadata in the
``.xplane.pb``. ``jax.profiler.ProfileData`` does not expose metadata stats,
so :func:`name_stacks` reads them from the serialized XSpace with a few
lines of protobuf decoding, one per op event in the order ``ProfileData``
gives the events, which are read as in ``bench/harness/trace.py``.

Host side. The program's spans (``repro.obs.trace.span``) are
``TraceAnnotation``s on a Python thread of ``/host:CPU``. Each program
launch there is a ``PJRT_LoadedExecutable_Execute linkage`` event inside a
``PjitFunction(<name>)`` event that names the program. A launch is tied to
its run on the device (``XLA Modules``) by order, not by time overlap:
runs lag their launch, the device clock sits up to about a millisecond off
the host's, and each chip runs its programs in launch order, so on each
chip the k-th launch of a program takes the k-th run of its module, and
launches that name no program take the runs left over in order, named by
their module.
A span owns the launches that start inside it.

Only what lies in the ``bench.window`` annotation counts: leaf ops clipped
to it, spans wholly inside it.
"""
from __future__ import annotations

import bisect
import functools
import statistics
from typing import Dict, List, NamedTuple, Optional, Tuple

from bench.harness import trace

SCOPE_PREFIX = "fl_"
PROGRAM_SPANS = ("train_chunk", "eval", "publish", "publish_snapshot",
                 "publish.encode", "publish.install", "checkpoint_save",
                 "checkpoint_load")
LAUNCH = "PJRT_LoadedExecutable_Execute linkage"
PJIT = "PjitFunction("


class ScopedOp(NamedTuple):
    op: trace.Op
    stack: Tuple[str, ...]   # name-stack components; () where it has none
    module: str              # the program run it lies in ('' if none)


class Run(NamedTuple):
    module: str              # ``jit_scan_chunk``, without the program id
    start: float
    end: float


class Launch(NamedTuple):
    t: float                 # the launch event's start on the host
    program: str             # named by the launch, else by its run
    runs: Tuple[Run, ...]    # its run on each chip, where the trace has it


class Span(NamedTuple):
    name: str
    start: float
    end: float
    launches: List[str]      # program named by each launch inside the span
    runs: List[Run]          # the device runs of those launches, all chips


class Scopes(NamedTuple):
    window: Tuple[float, float]
    ops: List[ScopedOp]      # the window's leaf ops, all chips
    spans: List[Span]        # the program's spans inside the window
    launches: List[Launch]   # every launch in the trace, in time order
    chips: int

    @property
    def scoped(self) -> bool:
        """Whether any op carries one of the round's phase scopes."""
        return any(in_phase(o) for o in self.ops)

    def seconds(self, ops: List[ScopedOp]) -> float:
        """Device seconds of these ops inside the window, per chip."""
        lo, hi = self.window
        return sum(min(o.op.end, hi) - max(o.op.start, lo)
                   for o in ops) / self.chips

    def under(self, scope: str) -> List[ScopedOp]:
        return [o for o in self.ops if scope in o.stack]

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]


def in_phase(op: ScopedOp) -> bool:
    """Whether the op lies under one of the round's ``fl_*`` scopes."""
    return any(part.startswith(SCOPE_PREFIX) for part in op.stack)


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, lo: int = 0, hi: Optional[int] = None):
    """``(field, value)`` for each field of the message in ``buf[lo:hi]``;
    a length-delimited value is its ``(start, end)`` in ``buf``."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def name_stacks(xspace: bytes) -> Dict[str, List[str]]:
    """``{device plane: [tf_op of each event of its XLA Ops lines]}`` from a
    serialized XSpace, in the events' order, '' where an op has none.

    An event names its metadata by id, and the name stack is a stat of that
    metadata: two programs may hold ops of the same HLO text, each with its
    own id and name stack.

    XSpace.planes = 1; XPlane.name = 2, .lines = 3, .event_metadata = 4
    and .stat_metadata = 5 (map entries: key 1, value 2); XLine.name = 2,
    .events = 4; XEvent.metadata_id = 1; XEventMetadata.stats = 5;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (a stat metadata
    id whose name is the string); XStatMetadata.id = 1, .name = 2.
    """
    def text(span):
        return bytes(xspace[span[0]:span[1]]).decode("utf-8", "replace")

    def entries(span):
        """``(key, value)`` of a map entry."""
        got = dict(_fields(xspace, *span))
        return got.get(1, 0), got.get(2)

    out: Dict[str, List[str]] = {}
    for field, plane in _fields(xspace):
        if field != 1:
            continue
        name, lines, metas, stat_names = "", [], [], {}
        for f, v in _fields(xspace, *plane):
            if f == 2:
                name = text(v)
            elif f == 3:
                lines.append(v)
            elif f == 4:
                metas.append(v)
            elif f == 5:
                _, value = entries(v)
                stat = dict(_fields(xspace, *value))
                stat_names[stat.get(1, 0)] = text(stat[2]) if 2 in stat else ""
        tf_op = [i for i, n in stat_names.items() if n == "tf_op"]
        if not name.startswith("/device:") or not tf_op:
            continue
        stack_of: Dict[int, str] = {}
        for entry in metas:
            mid, meta = entries(entry)
            for f, v in _fields(xspace, *meta) if meta else ():
                if f != 5:
                    continue
                stat = dict(_fields(xspace, *v))
                if stat.get(1) != tf_op[0]:
                    continue
                if 5 in stat:
                    stack_of[mid] = text(stat[5])
                elif 7 in stat:
                    stack_of[mid] = stat_names.get(stat[7], "")
        stacks: List[str] = []
        for line in lines:
            fields = list(_fields(xspace, *line))
            if any(f == 2 and text(v) == "XLA Ops" for f, v in fields):
                stacks += [stack_of.get(dict(_fields(xspace, *ev)).get(1, 0),
                                        "")
                           for f, ev in fields if f == 4]
        out[name] = stacks
    return out


def _stack(tf_op: str) -> Tuple[str, ...]:
    return tuple(tf_op.rstrip(":").split("/")) if tf_op else ()


def _tie(launches: List[Tuple[float, Optional[str]]], runs: List[Run]
         ) -> List[Optional[Run]]:
    """The run on one chip of each launch (both in time order), or None:
    the k-th launch of a program takes the k-th run of its module; launches
    that name no program take the runs left over, in order."""
    queues: Dict[str, List[Run]] = {}
    for run in runs:
        queues.setdefault(run.module, []).append(run)
    named = [queues[f"jit_{p}"].pop(0) if p and queues.get(f"jit_{p}")
             else None for _, p in launches]
    rest = iter(sorted((r for q in queues.values() for r in q),
                       key=lambda r: r.start))
    return [run if p else next(rest, None)
            for (_, p), run in zip(launches, named)]


def reduce(pd, stacks: Dict[str, List[str]], chips: int = 1) -> Scopes:
    """Scopes of a ``ProfileData`` over its ``bench.window``, with the name
    stacks :func:`name_stacks` read from the same trace."""
    host = pd.find_plane_with_name("/host:CPU")
    window = None
    spans: List[Tuple[str, float, float]] = []
    launches: List[Tuple[float, Optional[str]]] = []
    for line in host.lines if host is not None else ():
        pjit: List[Tuple[float, float, str]] = []
        marks: List[float] = []
        for name, s, e in trace._events(line):
            if name == trace.WINDOW_ANNOTATION:
                window = (s, e)
            elif name in PROGRAM_SPANS:
                spans.append((name, s, e))
            elif name.startswith(PJIT):
                pjit.append((s, e, name[len(PJIT):-1]))
            elif name == LAUNCH:
                marks.append(s)
        launches += [(t, trace._innermost(pjit, t)) for t in marks]
    if window is None:
        raise ValueError("the trace has no bench.window annotation")
    lo, hi = window
    launches.sort(key=lambda x: x[0])

    ops: List[ScopedOp] = []
    per_chip: List[List[Optional[Run]]] = []
    planes = [pd.find_plane_with_name(f"/device:TPU:{i}")
              for i in range(chips)]
    planes = [p for p in planes if p is not None]
    for plane in planes:
        chip_ops, chip_runs = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                chip_ops += [trace.Op(trace.op_name(n), n, s, e)
                             for n, s, e in trace._events(line)]
            elif line.name == "XLA Modules":
                chip_runs += [Run(n.split("(")[0], s, e)
                              for n, s, e in trace._events(line)]
        stack_of = stacks.get(plane.name) or [""] * len(chip_ops)
        if len(stack_of) != len(chip_ops):
            raise ValueError(f"{plane.name}: {len(stack_of)} name stacks "
                             f"for {len(chip_ops)} ops")
        stack_by_op = {id(o): st for o, st in zip(chip_ops, stack_of)}
        chip_runs.sort(key=lambda r: r.start)
        per_chip.append(_tie(launches, chip_runs))
        starts = [r.start for r in chip_runs]
        inside = [o for o in chip_ops if o.end > lo and o.start < hi]
        for o in trace._leaves(inside):
            j = bisect.bisect_right(starts, o.start) - 1
            run = chip_runs[j] if j >= 0 else None
            module = run.module if run and o.start <= run.end else ""
            ops.append(ScopedOp(o, _stack(stack_by_op[id(o)]), module))
    tied = []
    for k, (t, p) in enumerate(launches):
        runs = tuple(r for chip in per_chip if (r := chip[k]) is not None)
        tied.append(Launch(t, p or (runs[0].module[4:] if runs else ""),
                           runs))
    out = []
    for name, s, e in sorted(spans, key=lambda x: x[1]):
        if s < lo or e > hi:
            continue
        mine = [x for x in tied if s <= x.t <= e]
        out.append(Span(name, s, e, [x.program for x in mine],
                        [r for x in mine for r in x.runs]))
    return Scopes(window=window, ops=ops, spans=out, launches=tied,
                  chips=max(1, len(planes)))


@functools.lru_cache(maxsize=2)
def load(path: str, chips: int = 1) -> Scopes:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        xspace = f.read()
    return reduce(ProfileData.from_serialized_xspace(xspace),
                  name_stacks(xspace), chips)


def for_ctx(ctx) -> Optional[Scopes]:
    """The scopes of the traced run a reader is given: ``ctx.scopes`` where
    set, else the cell's last trace, the one the harness just reduced; None
    for an untraced run or a trace that cannot be read."""
    got = getattr(ctx, "scopes", None)
    if got is not None:
        return got
    summary = getattr(ctx, "summary", None)
    if summary is None:
        return None
    from bench.harness.device import CACHE_DIR

    try:
        path = trace.find_xplane(str(CACHE_DIR / "trace" / ctx.cell.name))
        return load(path, summary.chips)
    except (OSError, ValueError, IndexError):
        return None


def per_round_ms(ctx, scope: str) -> Optional[float]:
    """Device milliseconds per traced round of the leaf ops under a phase
    scope; None where no op carries the scope."""
    s = for_ctx(ctx)
    rounds = getattr(ctx, "traced_rounds", 0)
    if s is None or not rounds:
        return None
    ops = s.under(scope)
    if not ops:
        return None
    return 1e3 * s.seconds(ops) / rounds


def launches_per_span(s: Scopes, name: str) -> Optional[float]:
    """Median count of program launches inside each span of this name."""
    spans = s.named(name)
    if not spans:
        return None
    return float(statistics.median(len(x.launches) for x in spans))


def device_ms_per_span(s: Scopes, name: str) -> Optional[float]:
    """Device milliseconds, per span of this name and per chip, of the
    program runs its launches caused."""
    spans = s.named(name)
    runs = [r for x in spans for r in x.runs]
    if not runs:
        return None
    return 1e3 * sum(r.end - r.start for r in runs) / len(spans) / s.chips


def chunk_ops(s: Scopes) -> List[ScopedOp]:
    """Leaf ops in runs of the programs that ``train_chunk`` spans
    launched."""
    modules = {r.module for x in s.named("train_chunk") for r in x.runs}
    return [o for o in s.ops if o.module in modules]
