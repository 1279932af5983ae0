"""Cut a window out of a recorded chip trace and keep it as a text XSpace.

    python3 -m bench.tests.trace_fixture <in.xplane.pb> <out.txt.gz> \
        <start_ms> <length_ms>

The window starts ``start_ms`` after the recorded ``bench.window``
annotation opens. Kept: the device planes' ``XLA Ops`` and ``XLA Modules``
lines and every host line, restricted to events that overlap the window,
with a ``bench.window`` annotation over exactly the cut. Op names are
shortened to their head (name and result shape) plus the
``custom_call_target`` clause, which is what the reduction reads.
"""
from __future__ import annotations

import gzip
import re
import sys

_TARGET = re.compile(r'custom_call_target="[^"]*"')


def _short(name: str, head: int = 160) -> str:
    out = name[:head]
    m = _TARGET.search(name)
    if m and m.group(0) not in out:
        out += " ... " + m.group(0)
    return out


def _quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def cut(pd, start_ms: float, length_ms: float) -> str:
    host = pd.find_plane_with_name("/host:CPU")
    lo = None
    for line in host.lines:
        for e in line.events:
            if e.name == "bench.window":
                lo = e.start_ns
    if lo is None:
        raise ValueError("the trace has no bench.window annotation")
    lo += start_ms * 1e6
    hi = lo + length_ms * 1e6
    planes = []
    for pid, plane in enumerate(pd.planes):
        keep_lines = []
        for lid, line in enumerate(plane.lines):
            if plane.name.startswith("/device:") and \
                    line.name not in ("XLA Ops", "XLA Modules"):
                continue
            if not (plane.name.startswith("/device:")
                    or plane.name == "/host:CPU"):
                continue
            evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events
                   if e.start_ns + e.duration_ns > lo and e.start_ns < hi
                   and e.name != "bench.window"]
            if plane.name == "/host:CPU" and lid == 0:
                evs.append(("bench.window", lo, hi - lo))
            if evs:
                keep_lines.append((lid + 1, line.name, evs))
        if keep_lines:
            planes.append((pid + 1, plane.name, keep_lines))
    out = []
    for pid, pname, lines in planes:
        meta = {}
        out.append(f"planes {{\n  id: {pid}\n  name: {_quote(pname)}")
        for lid, lname, evs in lines:
            out.append(f"  lines {{\n    id: {lid}\n    name: {_quote(lname)}"
                       f"\n    timestamp_ns: 0")
            for name, start, dur in evs:
                short = _short(name)
                mid = meta.setdefault(short, len(meta) + 1)
                out.append(f"    events {{ metadata_id: {mid} offset_ps: "
                           f"{int(round(start * 1e3))} duration_ps: "
                           f"{int(round(dur * 1e3))} }}")
            out.append("  }")
        for short, mid in meta.items():
            out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                       f"name: {_quote(short)} }} }}")
        out.append("}")
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    from jax.profiler import ProfileData

    src, dst, start_ms, length_ms = (argv or sys.argv[1:])
    text = cut(ProfileData.from_file(src), float(start_ms), float(length_ms))
    with gzip.open(dst, "wt") as f:
        f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
