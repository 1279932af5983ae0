"""Run one benchmark cell once and print its result as the last stdout line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` (see ``bench/README.md``). ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace taken after the measured window. The run refuses (exit 2, no
result line) off the TPU, with fewer chips than the cell asks for, with
``REPRO_FORCE_REF`` set, or without the program beside the benchmark.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.harness import device  # noqa: E402
from bench.harness.device import ROOT, Refused  # noqa: E402


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def start(argv=None):
    """Everything before the cell's own work: the cell, the chip, the cache.

    Returns ``(args, cell, jax, devices, counter)``."""
    from bench.harness import spec

    args = parse_args(argv)
    device.check_program_present()
    cell = spec.find_cell(args.workload)
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    devices = device.check_chip(jax, cell.chips)
    cache = device.use_compile_cache(jax)
    counter = device.CompileCounter(jax)
    log(f"cell {cell.name} on {devices[0].device_kind} x{len(devices)}, "
        f"seed {args.seed}, {args.seconds} s, trace {args.trace}, "
        f"compile cache {cache}; JAX and the chip ready at "
        f"{time.perf_counter() - PROCESS_START:.3f} s")
    return args, cell, jax, devices, counter


def result_line(cell, correct, attempted, failed, metrics, dev, checks,
                breakdown=None) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return json.dumps(line)


def main(argv=None) -> int:
    try:
        args, cell, jax, devices, counter = start(argv)
    except Refused as r:
        log(f"refused: {r.reason}")
        return 2
    from bench.harness import serve, train

    drivers = {"train": train.run, "serve_open": serve.run}
    kind = cell.traffic["kind"]
    if kind not in drivers:
        log(f"refused: traffic kind {kind!r} has no driver; known: "
            f"{sorted(drivers)}")
        return 2
    out = drivers[kind](args, cell, jax, devices, counter, PROCESS_START, log)
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(result_line(cell, out["correct"], out["attempted"], out["failed"],
                      out["metrics"], out["device"], out["checks"],
                      out.get("breakdown")), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
