"""Sharded round engine: rounds/sec + bytes-moved-per-device vs mesh size.

Measures the ``backend="shard"`` engine (shard_map data-parallel FL rounds:
row-sharded tables, one cohort block per device, collective payload
movement) against the single-device ``backend="scan"`` baseline, for all
four strategies x {fp32, int8} wire formats at D in {1, 2, 4, 8} devices.

CPU has one physical device, and ``--xla_force_host_platform_device_count``
only takes effect before jax initializes — so every D runs in its own worker
subprocess with fake CPU devices. The workers are CPU-only
(``JAX_PLATFORMS=cpu``) even on a TPU host, where the parent holds the chip
and a child reaching for it would fail or hang. Fake devices share the
host's cores:
rounds/sec at D>1 measures the *overhead* of the sharded program
(collectives + smaller per-device batches on shared silicon), not a
speedup — the speedup story is the per-device numbers: each device holds
1/D of every (M, K) table and solves 1/D of the cohort, while the bytes
crossing the interconnect stay payload-sized (reported here as
``collective_bytes_per_device_per_round``, where int8 cuts the dominant
downlink all-gather 4x).

Acceptance gates checked here: D=1 sharded within 10% of the plain scan
engine, and D=1 bit-parity with it (the D>1 parity matrix is tier-1:
``tests/test_sharded_rounds.py``).

Writes ``BENCH_sharded_rounds.json`` (schema shared with
``BENCH_round_engine.json``: every rounds/sec figure pairs with a
``bytes_per_round`` dict).

Usage:  PYTHONPATH=src python -m benchmarks.sharded_rounds [--quick|--dry-run]
        (internal)  ... --worker D
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Dict, Optional, Sequence

import numpy as np

from benchmarks.common import markdown_table, per_round_payload_bytes

OUT_PATH = "BENCH_sharded_rounds.json"
WORKER_MARK = "SHARDED_WORKER_JSON:"
STRATEGIES = ("bts", "random", "magnitude", "full")
CODECS = ("fp32", "int8")
MESH_SIZES = (1, 2, 4, 8)
REPEATS = 3


def make_data(users: int, items: int, density: float = 0.02, seed: int = 0):
    rng = np.random.default_rng(seed)
    train = (rng.random((users, items)) < density).astype(np.float32)
    test = (rng.random((users, items)) < density / 4).astype(np.float32)
    return train, test


def _scale(quick: bool) -> Dict:
    users, items = (500, 2000) if quick else (2000, 10_000)
    return {"users": users, "items": items, "k": 25, "theta": 100,
            "keep_fraction": 0.1, "rounds": 20 if quick else 40}


def collective_bytes_per_device(strategy: str, codec: str, d: int,
                                num_select: int, k: int) -> int:
    """Bytes each device RECEIVES per round from the engine's collectives.

    Mirrors the implementation's schedule (see ``server_round_step``):
      * 1 all-gather of the *encoded* Q* candidates (the int8 wire moves
        codes + per-row f32 scales — 4x less than fp32 rows),
      * 1 all-gather of the (M_s, K) f32 partial gradients (ordered psum),
      * (M_s, K) f32 row gathers of the tables the round touches: 3 for the
        Adam commit (m, v, params), +2 for the BTS reward buffers, +1 for
        the topk codec residual. Scatters are shard-local (0 bytes).
    Each all-gather of an (M_s, .) candidate delivers the other D-1 shards'
    copies.
    """
    if d <= 1:
        return 0
    fp_rows = num_select * k * 4
    down = per_round_payload_bytes(num_select, k, codec=codec)["down"]
    row_gathers = 3 + (2 if strategy == "bts" else 0) \
        + (1 if codec == "topk" else 0)
    return (d - 1) * (down + fp_rows * (1 + row_gathers))


# ------------------------------------------------------------------ #
# timing (runs inside the worker; needs the right device count)
# ------------------------------------------------------------------ #
def _make_sampler(train, test, cfg, rounds: int):
    """Compile one engine; return ``sample() -> rounds/sec`` (warmed up)."""
    import jax
    import jax.numpy as jnp

    from repro.federated.simulation import (
        _build, _make_round_fn, make_sharded_round_runner,
    )

    train_j = jnp.asarray(train, jnp.float32)
    setup = _build(train_j, jnp.asarray(test, jnp.float32), cfg)
    cohorts = np.resize(setup.cohorts, (rounds,) + setup.cohorts.shape[1:])

    if cfg.backend == "shard":
        run_chunk, state0 = make_sharded_round_runner(train_j, setup, cfg)
    else:
        round_fn = _make_round_fn(train_j, setup, cfg.cohort_shards)

        def scan_chunk(state, ch):
            def body(st, cohort):
                st, _ = round_fn(st, cohort)
                return st, None
            return jax.lax.scan(body, state, ch)

        compiled = jax.jit(scan_chunk)
        state0 = setup.state0

        def run_chunk(state, ch):
            return compiled(state, jnp.asarray(ch))

    def sample() -> float:
        t0 = time.perf_counter()
        state, _ = run_chunk(state0, cohorts)
        jax.block_until_ready(state.q)
        return rounds / (time.perf_counter() - t0)

    sample()                                       # warmup / compile
    return sample


def _time_engine(train, test, cfg, rounds: int) -> float:
    sample = _make_sampler(train, test, cfg, rounds)
    return max(sample() for _ in range(REPEATS))


def _worker(d: int, quick: bool) -> Dict:
    """Measure every strategy x codec at mesh size ``d`` (current process
    must already see exactly ``d`` devices)."""
    import jax

    from repro.federated.simulation import FLSimConfig

    assert len(jax.devices()) >= d, (
        f"worker expected {d} devices, found {len(jax.devices())} — "
        "launch via the parent (it sets XLA_FLAGS before jax init)")
    sc = _scale(quick)
    train, test = make_data(sc["users"], sc["items"])
    out: Dict = {"d": d, "sharded": {}, "scan_baseline": {}}
    for strategy in STRATEGIES:
        out["sharded"][strategy] = {}
        if d == 1:
            out["scan_baseline"][strategy] = {}
        for codec in CODECS:
            base = dict(strategy=strategy, codec=codec,
                        keep_fraction=sc["keep_fraction"], theta=sc["theta"],
                        num_factors=sc["k"], seed=0, rounds=sc["rounds"],
                        eval_every=10 * sc["rounds"])
            num_select = sc["items"] if strategy == "full" \
                else int(round(sc["keep_fraction"] * sc["items"]))
            bytes_pr = per_round_payload_bytes(
                num_select, sc["k"], codec=codec,
                theta=min(sc["theta"], sc["users"]))
            cfg = FLSimConfig(backend="shard", mesh_shards=d, **base)
            if d == 1:
                # the D=1-within-10%-of-scan gate: alternate samples of the
                # two engines so CPU drift hits both equally (best-of)
                shard_sample = _make_sampler(train, test, cfg, sc["rounds"])
                scan_sample = _make_sampler(train, test, FLSimConfig(**base),
                                            sc["rounds"])
                # the two D=1 programs are near-identical; the observed
                # spread is host noise, so take best-of over enough
                # alternating pairs for both bests to converge
                rps, rps_scan = 0.0, 0.0
                for _ in range(2 * REPEATS + 2):
                    rps_scan = max(rps_scan, scan_sample())
                    rps = max(rps, shard_sample())
                out["scan_baseline"][strategy][codec] = {
                    "rounds_per_sec": rps_scan,
                    "bytes_per_round": bytes_pr,
                }
            else:
                rps = _time_engine(train, test, cfg, sc["rounds"])
            out["sharded"][strategy][codec] = {
                "rounds_per_sec": rps,
                "bytes_per_round": bytes_pr,
                "collective_bytes_per_device_per_round":
                    collective_bytes_per_device(strategy, codec, d,
                                                num_select, sc["k"]),
            }
    return out


# ------------------------------------------------------------------ #
# orchestration (parent process)
# ------------------------------------------------------------------ #
def _spawn_worker(d: int, quick: bool) -> Dict:
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.launch.mesh import fake_cpu_devices_env

    env = fake_cpu_devices_env(d)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "benchmarks.sharded_rounds",
           "--worker", str(d)] + (["--quick"] if quick else [])
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=os.getcwd(), timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded_rounds worker D={d} failed:\n{proc.stderr[-4000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith(WORKER_MARK):
            return json.loads(line[len(WORKER_MARK):])
    raise RuntimeError(
        f"worker D={d} produced no result line:\n{proc.stdout[-2000:]}")


def run(quick: bool = False) -> Dict:
    sc = _scale(quick)
    out: Dict = {
        "scale": sc,
        "mesh_sizes": list(MESH_SIZES),
        "note": ("fake CPU devices share the host cores: D>1 rounds/sec "
                 "measures sharding overhead, not speedup; per-device "
                 "state is 1/D of every (M, K) table"),
        "sharded": {}, "scan_baseline": {},
    }
    for d in MESH_SIZES:
        res = _spawn_worker(d, quick)
        out["sharded"][str(d)] = res["sharded"]
        if d == 1:
            out["scan_baseline"] = res["scan_baseline"]
        print(f"  measured D={d}")

    # acceptance gate: D=1 sharded within 10% of the plain scan engine
    out["d1_vs_scan"] = {}
    worst = 1.0
    for strategy in STRATEGIES:
        for codec in CODECS:
            r_shard = out["sharded"]["1"][strategy][codec]["rounds_per_sec"]
            r_scan = out["scan_baseline"][strategy][codec]["rounds_per_sec"]
            ratio = r_shard / r_scan
            out["d1_vs_scan"][f"{strategy}/{codec}"] = ratio
            worst = min(worst, ratio)
    out["d1_min_ratio_vs_scan"] = worst

    print(f"\n## Sharded rounds — rounds/sec vs mesh size "
          f"(M={sc['items']}, K={sc['k']}, Theta={sc['theta']}, "
          f"{int((1 - sc['keep_fraction']) * 100)}% payload cut)\n")
    rows = []
    for strategy in STRATEGIES:
        for codec in CODECS:
            cells = [out["sharded"][str(d)][strategy][codec]
                     for d in MESH_SIZES]
            rows.append(
                (f"{strategy}/{codec}",
                 f"{out['scan_baseline'][strategy][codec]['rounds_per_sec']:.1f}",
                 *(f"{c['rounds_per_sec']:.1f}" for c in cells),
                 f"{cells[-1]['collective_bytes_per_device_per_round'] / 1e6:.2f}"))
    print(markdown_table(
        ("strategy/codec", "scan (r/s)",
         *(f"D={d} (r/s)" for d in MESH_SIZES),
         "D=8 coll. MB/dev/round"), rows))
    print(f"\nD=1 sharded vs scan: worst ratio {worst:.2f} "
          f"(target >= 0.90)")

    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {OUT_PATH}")
    return out


def dry_run() -> Dict:
    """Two sharded toy rounds on whatever devices exist (D=1 in CI) plus a
    bitwise check against the scan engine: the shard_map program must build,
    execute and agree."""
    from dataclasses import replace

    from repro.federated.simulation import FLSimConfig, run_fcf_simulation

    train, test = make_data(40, 64)
    cfg = FLSimConfig(strategy="bts", keep_fraction=0.25, theta=8,
                      num_factors=8, rounds=2, eval_every=20, seed=0,
                      record_selections=True)
    scan = run_fcf_simulation(train, test, cfg)
    shard = run_fcf_simulation(
        train, test, replace(cfg, backend="shard", mesh_shards=1))
    assert np.array_equal(scan.selections, shard.selections)
    assert np.array_equal(np.asarray(scan.server_state.q),
                          np.asarray(shard.server_state.q))
    print("[dry-run] sharded_rounds — 2-round toy shard_map scan OK, "
          "bitwise equal to the scan engine")
    return {"dry_run": True, "d1_bitwise_equal": True}


def main(argv: Optional[Sequence[str]] = None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller scale for smoke runs")
    ap.add_argument("--dry-run", action="store_true",
                    help="toy shard rounds on current devices only")
    ap.add_argument("--worker", type=int, default=None,
                    help=argparse.SUPPRESS)   # internal: one mesh size
    args = ap.parse_args(argv)
    if args.worker is not None:
        res = _worker(args.worker, args.quick)
        print(WORKER_MARK + json.dumps(res))
        return res
    return dry_run() if args.dry_run else run(quick=args.quick)


if __name__ == "__main__":
    main()
