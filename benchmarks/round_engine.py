"""Round-engine throughput: legacy loop vs per-round jitted step vs lax.scan.

Measures steady-state FL rounds/sec of one server round at MIND-like scale
(M = 10k items, K = 25 factors, Theta = 100 users/commit, 90% payload cut)
for three execution models:

  * ``legacy`` — the pre-refactor engine, reproduced faithfully: per-round
    Python through the mutable ``FCFServer`` / ``PayloadSelector`` objects
    (selection, Adam commit and reward updates run eagerly op-by-op; only
    the client solve is jitted) with the seed's original client math (naive
    (b,m,k,l) einsum normal equations + LU solve). This is how the seed
    reproduction drove every round, and it is the baseline the refactor's
    speedup claim is measured against.
  * ``python`` — the fused pure ``server_round_step`` jitted once and
    dispatched per round from Python (simulation ``backend="python"``).
  * ``scan``   — the same step compiled into one ``jax.lax.scan`` program
    (simulation ``backend="scan"``, the default engine).

Compilation is excluded (warmup call per engine); the headline number is
the legacy -> scan speedup, with a >= 5x acceptance bar for the bandit
strategy on CPU. Writes ``BENCH_round_engine.json`` in the cwd.

Usage:  PYTHONPATH=src python -m benchmarks.round_engine [--quick] [--dry-run]
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, Optional, Sequence

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.cf.local import item_gradients
from repro.cf.server import FCFServer, FCFServerConfig
from repro.core.payload import make_selector
from repro.federated.simulation import (
    FLSimConfig, _build, _make_round_fn, run_fcf_simulation,
)
from repro.obs import InMemorySink, ObsConfig

from benchmarks.common import markdown_table, per_round_payload_bytes

OUT_PATH = "BENCH_round_engine.json"
REPEATS = 3   # best-of repeats per engine (CPU benchmarks are noisy)


def make_data(users: int, items: int, density: float = 0.02, seed: int = 0):
    rng = np.random.default_rng(seed)
    train = (rng.random((users, items)) < density).astype(np.float32)
    test = (rng.random((users, items)) < density / 4).astype(np.float32)
    return train, test


def _setup(train, test, cfg: FLSimConfig):
    train_j = jnp.asarray(train, jnp.float32)
    setup = _build(train_j, jnp.asarray(test, jnp.float32), cfg)
    return train_j, setup, _make_round_fn(train_j, setup, blocks=1,
                                          is_async=False, telemetry=False)


@partial(jax.jit, static_argnames=("l2", "alpha"))
def _seed_solve_user_factors(q, x, l2=1.0, alpha=4.0):
    """The seed's original Eq. 3 solve (pre hot-path optimization): naive
    per-user (b, m, k, l) einsum for the normal equations + batched LU."""
    k = q.shape[-1]
    gram = q.T @ q
    corr = jnp.einsum("bm,mk,ml->bkl", x, q, q)
    lhs = gram[None] + alpha * corr + l2 * jnp.eye(k, dtype=q.dtype)[None]
    rhs = (1.0 + alpha) * (x @ q)
    return jnp.linalg.solve(lhs, rhs[..., None])[..., 0]


def _seed_local_update(q, x, cf_cfg):
    p = _seed_solve_user_factors(q, x, l2=cf_cfg.l2, alpha=cf_cfg.alpha)
    g = item_gradients(q, p, x, l2=cf_cfg.l2, alpha=cf_cfg.alpha)
    return p, g


def time_legacy(train, test, cfg: FLSimConfig, rounds: int) -> float:
    """The seed's execution model: mutable objects, eager server math."""
    train_j, setup, _ = _setup(train, test, cfg)
    users = train.shape[0]
    selector = make_selector(
        cfg.strategy, num_arms=train.shape[1], dim=cfg.num_factors,
        keep_fraction=cfg.keep_fraction, seed=cfg.seed + 13)
    server = FCFServer(
        item_factors=setup.state0.q, selector=selector,
        config=FCFServerConfig(theta=cfg.theta))
    rng = np.random.default_rng(cfg.seed + 31)

    def one_round():
        q_star = server.begin_round()
        cohort = rng.choice(users, size=min(cfg.theta, users), replace=False)
        x_sub = train_j[jnp.asarray(cohort)][:, server.selected]
        _, grads = _seed_local_update(q_star, x_sub, setup.cf_cfg)
        server.receive(grads, num_users=len(cohort))

    for _ in range(3):                     # warmup / compile
        one_round()
    jax.block_until_ready(server.item_factors)
    best = 0.0
    for _ in range(REPEATS):               # best-of: least interference
        t0 = time.perf_counter()
        for _ in range(rounds):
            one_round()
        jax.block_until_ready(server.item_factors)
        best = max(best, rounds / (time.perf_counter() - t0))
    return best


def time_python(train, test, cfg: FLSimConfig, rounds: int) -> float:
    """Fused step, per-round dispatch (simulation backend="python")."""
    _, setup, round_fn = _setup(train, test, cfg)
    step = jax.jit(round_fn)
    cohorts = jnp.asarray(setup.cohorts)
    state, _ = step(setup.state0, cohorts[0])      # warmup / compile
    jax.block_until_ready(state.q)
    best = 0.0
    for _ in range(REPEATS):
        state = setup.state0
        t0 = time.perf_counter()
        for t in range(rounds):
            state, _ = step(state, cohorts[t % cohorts.shape[0]])
        jax.block_until_ready(state.q)
        best = max(best, rounds / (time.perf_counter() - t0))
    return best


def time_scan(train, test, cfg: FLSimConfig, rounds: int) -> float:
    """Whole-chunk lax.scan compilation (simulation backend="scan")."""
    _, setup, round_fn = _setup(train, test, cfg)

    def scan_chunk(state, cohorts):
        def body(st, cohort):
            st, _ = round_fn(st, cohort)
            return st, None
        return jax.lax.scan(body, state, cohorts)

    run_chunk = jax.jit(scan_chunk)
    cohorts = jnp.asarray(
        np.resize(setup.cohorts, (rounds,) + setup.cohorts.shape[1:]))
    state, _ = run_chunk(setup.state0, cohorts)    # warmup / compile
    jax.block_until_ready(state.q)
    best = 0.0
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        state, _ = run_chunk(setup.state0, cohorts)
        jax.block_until_ready(state.q)
        best = max(best, rounds / (time.perf_counter() - t0))
    return best


def regret_series(train, test, rounds: int, every: int = 10) -> Dict:
    """Cumulative pseudo-regret of the scan engine's bandit, via telemetry.

    Runs the default strategy with in-loop observability on
    (:class:`repro.obs.ObsConfig` + an in-memory sink) and reads the
    ``cum_regret`` series straight off the round-telemetry stream — the
    traced port of ``core/regret.RegretTracker`` that now computes inside
    the compiled scan. Subsampled to every ``every`` rounds (plus the
    final round) to keep the committed artifact small.
    """
    sink = InMemorySink()
    cfg = FLSimConfig(
        strategy="bts", keep_fraction=0.1, theta=100, num_factors=25,
        rounds=rounds, eval_every=rounds, seed=0,
        obs=ObsConfig(enabled=True, sink=sink))
    run_fcf_simulation(train, test, cfg)
    cum = [e["cum_regret"] for e in sink.events]
    idx = list(range(every - 1, len(cum), every))
    if not idx or idx[-1] != len(cum) - 1:
        idx.append(len(cum) - 1)
    return {
        "strategy": "bts",
        "rounds": rounds,
        "every": every,
        "round_ids": [i + 1 for i in idx],
        "cum_regret": [round(cum[i], 4) for i in idx],
        "final_cum_regret": round(cum[-1], 4),
    }


def run(quick: bool = False) -> Dict:
    # MIND-like scale (paper Table 2): 10k items, K=25, Theta=100, 90% cut
    users, items = (1000, 2000) if quick else (5000, 10_000)
    scan_rounds = 100 if quick else 200
    loop_rounds = 30 if quick else 60       # dispatch-bound: keep it short
    train, test = make_data(users, items)
    base = dict(keep_fraction=0.1, theta=100, num_factors=25, seed=0,
                rounds=scan_rounds, eval_every=10 * scan_rounds)

    out: Dict = {
        "scale": {"users": users, "items": items, "k": 25, "theta": 100,
                  "keep_fraction": 0.1},
        "strategies": {},
    }
    rows = []
    for strategy in ("bts", "random", "magnitude", "full"):
        cfg = FLSimConfig(strategy=strategy, **base)
        rps_legacy = time_legacy(train, test, cfg, loop_rounds)
        rps_py = time_python(train, test, cfg, loop_rounds)
        rps_scan = time_scan(train, test, cfg, scan_rounds)
        speedup = rps_scan / rps_legacy
        num_select = items if strategy == "full" \
            else int(round(cfg.keep_fraction * items))
        out["strategies"][strategy] = {
            "legacy_rounds_per_sec": rps_legacy,
            "python_rounds_per_sec": rps_py,
            "scan_rounds_per_sec": rps_scan,
            "speedup_scan_vs_legacy": speedup,
            "speedup_scan_vs_python": rps_scan / rps_py,
            # shared perf-trajectory schema with BENCH_sharded_rounds.json:
            # every rounds/sec figure pairs with the payload bytes one round
            # moves at this configuration (codec=fp32, theta uplink users)
            "bytes_per_round": per_round_payload_bytes(
                num_select, cfg.num_factors, codec=cfg.codec,
                theta=min(cfg.theta, users)),
        }
        rows.append((strategy, f"{rps_legacy:.1f}", f"{rps_py:.1f}",
                     f"{rps_scan:.1f}", f"{speedup:.1f}x"))

    out["regret"] = regret_series(train, test, rounds=scan_rounds)
    print("\n## Round engine — rounds/sec "
          f"(M={items}, K=25, Theta=100, 90% payload cut)\n")
    print(markdown_table(
        ("strategy", "legacy loop (r/s)", "fused step (r/s)",
         "lax.scan (r/s)", "scan vs legacy"), rows))
    print(f"\nbts cumulative regret after {scan_rounds} rounds: "
          f"{out['regret']['final_cum_regret']:.2f} "
          f"(telemetry series, every {out['regret']['every']} rounds)")

    with open(OUT_PATH, "w") as f:
        json.dump(out, f, indent=1)
    print(f"\nwrote {OUT_PATH}")
    bts = out["strategies"]["bts"]["speedup_scan_vs_legacy"]
    print(f"bts scan-vs-legacy speedup: {bts:.1f}x (target >= 5x)")
    return out


def dry_run() -> Dict:
    """Two scan rounds at toy scale: the engine must build and execute.

    Also exercises the telemetry-backed regret series (4 toy rounds with
    observability on) so the obs wiring is covered by the CI smoke.
    """
    train, test = make_data(40, 60)
    cfg = FLSimConfig(strategy="bts", keep_fraction=0.25, theta=8,
                      num_factors=8, rounds=2, eval_every=20, seed=0)
    rps = time_scan(train, test, cfg, rounds=2)
    sink = InMemorySink()
    tiny = FLSimConfig(strategy="bts", keep_fraction=0.25, theta=8,
                       num_factors=8, rounds=4, eval_every=20, seed=0,
                       obs=ObsConfig(enabled=True, sink=sink))
    run_fcf_simulation(train, test, tiny)
    cum = [e["cum_regret"] for e in sink.events]
    assert len(cum) == 4 and all(b >= a for a, b in zip(cum, cum[1:])), \
        f"telemetry regret series not cumulative: {cum}"
    print(f"[dry-run] round_engine — 2-round toy scan OK "
          f"({rps:.0f} rounds/s); telemetry regret series OK "
          f"(cum_regret[-1]={cum[-1]:.3f})")
    return {"dry_run": True, "toy_rounds_per_sec": rps,
            "toy_cum_regret": cum[-1]}


def main(argv: Optional[Sequence[str]] = None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller scale for smoke runs")
    ap.add_argument("--dry-run", action="store_true",
                    help="two toy rounds through the scan engine only")
    args = ap.parse_args(argv)
    return dry_run() if args.dry_run else run(quick=args.quick)


if __name__ == "__main__":
    main()
