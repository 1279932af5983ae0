"""Cut one chunk boundary out of a recorded training trace, keeping the name
stacks of the device ops and the program's spans on the host.

    python3 -m bench.tests.scope_fixture <in.xplane.pb> <out.txt.gz> [<n>]

The cut runs from the opening of the ``n``-th ``train_chunk`` span inside
the recorded ``bench.window`` (default 1, the second) to the close of the
``publish`` span that follows it (2 us after, against rounding; the next
``train_chunk`` opens some microseconds later): one whole chunk boundary,
which becomes the fixture's ``bench.window``. Kept:

  * the device ops (``XLA Ops``) overlapping the cut, their names shortened
    as ``bench.tests.trace_fixture`` does, each with its ``tf_op`` name
    stack as a stat of its event metadata;
  * the program's spans on the host overlapping the cut, and each launch
    (``PjitFunction(...)`` and the launch marker) made from 20 ms before
    the cut or whose run ends after that, with that run (``XLA Modules``),
    so that every launch is tied to the same run as in the whole trace.
"""
from __future__ import annotations

import gzip
import sys

from bench.harness import scopes
from bench.tests.trace_fixture import _quote, _short

_CONTEXT_S = 20e-3
_TAIL_NS = 2e3


def cut(pd, xspace: bytes, n: int = 1) -> str:
    stacks = scopes.name_stacks(xspace)
    whole = scopes.reduce(pd, stacks)
    host = pd.find_plane_with_name("/host:CPU")
    events = [e for line in host.lines for e in line.events]
    lo_w, hi_w = whole.window
    chunks = sorted(e.start_ns for e in events if e.name == "train_chunk"
                    and lo_w <= e.start_ns * 1e-9 <= hi_w)
    lo = chunks[n]
    hi = min(e.end_ns for e in events
             if e.name == "publish" and e.start_ns > lo) + _TAIL_NS
    since = lo * 1e-9 - _CONTEXT_S
    kept = [x for x in whole.launches if x.t < hi * 1e-9 and (
        x.t >= since or any(r.end > since for r in x.runs))]
    marks = {x.t for x in kept}
    runs = {(r.module, r.start) for x in kept for r in x.runs}
    first = min(marks, default=since) * 1e9

    def keep_host(e) -> bool:
        if e.name == scopes.LAUNCH:
            return e.start_ns * 1e-9 in marks
        if e.name.startswith(scopes.PJIT):
            return e.end_ns >= first and e.start_ns < hi
        return (e.name in scopes.PROGRAM_SPANS and e.end_ns > lo
                and e.start_ns < hi)

    planes = []
    for pid, plane in enumerate(pd.planes):
        device = plane.name.startswith("/device:")
        if not (device or plane.name == "/host:CPU"):
            continue
        op_stacks = iter(stacks.get(plane.name) or ())
        keep_lines = []
        for lid, line in enumerate(plane.lines):
            if line.name == "XLA Ops" and device:
                evs = [(e, next(op_stacks, "")) for e in line.events]
                evs = [(e, st) for e, st in evs
                       if e.start_ns + e.duration_ns > lo and e.start_ns < hi]
            elif line.name == "XLA Modules" and device:
                evs = [(e, "") for e in line.events
                       if (e.name.split("(")[0], e.start_ns * 1e-9) in runs]
            elif not device:
                evs = [(e, "") for e in line.events if keep_host(e)]
            else:
                continue
            if evs:
                keep_lines.append((lid + 1, line.name, [
                    (e.name, st, e.start_ns, e.duration_ns)
                    for e, st in evs]))
        if keep_lines:
            planes.append((pid + 1, plane.name, keep_lines))
    host_lines = next(p[2] for p in planes if p[1] == "/host:CPU")
    host_lines.append((len(host_lines) + 1, "bench",
                       [("bench.window", "", lo, hi - lo)]))
    out = []
    for pid, pname, lines in planes:
        meta = {}   # (short name, name stack) -> metadata id
        out.append(f"planes {{\n  id: {pid}\n  name: {_quote(pname)}")
        for lid, lname, evs in lines:
            out.append(f"  lines {{\n    id: {lid}\n    name: {_quote(lname)}"
                       f"\n    timestamp_ns: 0")
            for name, stack, start, dur in evs:
                mid = meta.setdefault((_short(name), stack), len(meta) + 1)
                out.append(f"    events {{ metadata_id: {mid} offset_ps: "
                           f"{int(round(start * 1e3))} duration_ps: "
                           f"{int(round(dur * 1e3))} }}")
            out.append("  }")
        for (short, stack), mid in meta.items():
            stat = (f" stats {{ metadata_id: 1 str_value: {_quote(stack)} }}"
                    if stack else "")
            out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                       f"name: {_quote(short)}{stat} }} }}")
        if any(stack for _, stack in meta):
            out.append('  stat_metadata { key: 1 value { id: 1 name: '
                       '"tf_op" } }')
        out.append("}")
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    from jax.profiler import ProfileData

    args = argv or sys.argv[1:]
    src, dst = args[0], args[1]
    with open(src, "rb") as f:
        xspace = f.read()
    text = cut(ProfileData.from_serialized_xspace(xspace), xspace,
               int(args[2]) if len(args) > 2 else 1)
    with gzip.open(dst, "wt") as f:
        f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
