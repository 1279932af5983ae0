"""Bytes-vs-quality Pareto sweep over (strategy x codec x payload fraction).

The paper reduces payload along ONE axis — which rows move (bandit
selection, ~90% fewer rows at keep=0.1). The compression subsystem adds
the second axis — bits per row. This benchmark charts the joint frontier:
for each (strategy, codec, keep_fraction) cell it runs the scan engine,
then reports

  * bytes/round (down + up, priced by ``compress.wire_bytes`` — the same
    accounting the engine's traced counters use),
  * reduction vs the paper's reference point (FCF full payload, fp32),
  * reduction vs the SAME selection level in fp32 (the pure codec win),
  * precision@10 / F1 degradation vs the full-fp32 upper bound,
  * steady-state rounds/sec of the compiled engine (is the codec free?).

Headline rows (asserted, persisted to ``BENCH_payload_compression.json``):
the paper's ~90% reduction cell (bts, fp32, keep=0.1) and how far
int8+BTS pushes beyond it (>= 4x the bytes-reduction at matched payload
fraction, i.e. combining both axes).

Usage:  PYTHONPATH=src python -m benchmarks.payload_compression
        [--quick] [--dry-run] [--dataset movielens-mini]
"""
from __future__ import annotations

import argparse
import json
import time
from dataclasses import replace
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress import CODECS, CodecConfig, direction_configs, wire_bytes
from repro.data.synthetic import load_dataset
from repro.federated.simulation import (
    FLSimConfig, run_fcf_simulation, _build, _make_round_fn, _num_select,
)

from benchmarks.common import markdown_table

OUT_PATH = "BENCH_payload_compression.json"

STRATEGIES = ("bts", "random")
KEEPS = (0.10, 0.25)
# the third payload axis (ROADMAP follow-up): how much of each row the topk
# uplink keeps. 0.25 is the codec's default; the sweep charts the frontier.
TOPK_FRACTIONS = (0.125, 0.25, 0.5)


def _variants(codecs: Sequence[str],
              topk_fractions: Sequence[float]) -> List[Dict]:
    """Expand the codec list into sweep cells.

    ``topk`` fans out over ``topk_fractions`` (labelled ``topk@f``) and
    ``int4`` gains an error-feedback twin (``int4+ef`` — the uplink carries
    the quantization residual forward, same mechanism as topk's EF).
    """
    out: List[Dict] = []
    for codec in codecs:
        if codec == "topk":
            for f in topk_fractions:
                out.append({"codec": codec, "label": f"topk@{f:g}",
                            "kwargs": {"codec_topk_fraction": f}})
        elif codec == "int4":
            out.append({"codec": codec, "label": "int4", "kwargs": {}})
            out.append({"codec": codec, "label": "int4+ef",
                        "kwargs": {"codec_int4_error_feedback": True}})
        else:
            out.append({"codec": codec, "label": codec, "kwargs": {}})
    return out


def _per_round_bytes(cfg: FLSimConfig, num_items: int) -> Dict[str, int]:
    """Bytes/round for one cell — the engine's own row count (_num_select)
    and wire pricing (compress.wire_bytes), so this can't drift from the
    simulation's traced counters."""
    codec_cfg = CodecConfig(name=cfg.codec,
                            topk_fraction=cfg.codec_topk_fraction,
                            error_feedback=cfg.codec_error_feedback,
                            int4_error_feedback=cfg.codec_int4_error_feedback)
    down_cfg, up_cfg = direction_configs(codec_cfg)
    m_s = _num_select(cfg, num_items)
    down = wire_bytes(down_cfg, m_s, cfg.num_factors)
    up = wire_bytes(up_cfg, m_s, cfg.num_factors) * cfg.theta
    return {"down": down, "up": up, "total": down + up}


def _rounds_per_sec(train, test, cfg: FLSimConfig, rounds: int = 60) -> float:
    """Steady-state scan throughput of the codec-routed engine."""
    train_j = jnp.asarray(train, jnp.float32)
    setup = _build(train_j, jnp.asarray(test, jnp.float32), cfg)
    round_fn = _make_round_fn(train_j, setup, blocks=1, is_async=False,
                              telemetry=False)

    def scan_chunk(state, cohorts):
        def body(st, cohort):
            st, _ = round_fn(st, cohort)
            return st, None
        return jax.lax.scan(body, state, cohorts)

    run_chunk = jax.jit(scan_chunk)
    cohorts = jnp.asarray(
        np.resize(setup.cohorts, (rounds,) + setup.cohorts.shape[1:]))
    state, _ = run_chunk(setup.state0, cohorts)        # warmup / compile
    jax.block_until_ready(state.q)
    best = 0.0
    for _ in range(2):
        t0 = time.perf_counter()
        state, _ = run_chunk(setup.state0, cohorts)
        jax.block_until_ready(state.q)
        best = max(best, rounds / (time.perf_counter() - t0))
    return best


def run(dataset: str = "movielens-mini", rounds: int = 200, theta: int = 50,
        strategies: Sequence[str] = STRATEGIES,
        codecs: Sequence[str] = CODECS,
        keeps: Sequence[float] = KEEPS,
        topk_fractions: Sequence[float] = TOPK_FRACTIONS,
        time_rounds: int = 60, seed: int = 0,
        out_path: Optional[str] = OUT_PATH) -> Dict:
    spec, train, test = load_dataset(dataset, seed=seed)
    num_items = train.shape[1]
    base = FLSimConfig(rounds=rounds, theta=theta, eval_every=max(rounds // 8, 1),
                       eval_users=min(256, train.shape[0]), seed=seed)

    # the paper's reference point: FCF full payload, fp32 wire
    full_cfg = replace(base, strategy="full", keep_fraction=1.0)
    full_res = run_fcf_simulation(train, test, full_cfg)
    full_bytes = _per_round_bytes(full_cfg, num_items)["total"]
    full_p10 = full_res.final["precision"]
    full_f1 = full_res.final["f1"]

    cells: List[Dict] = []
    for strategy in strategies:
        for keep in keeps:
            for var in _variants(codecs, topk_fractions):
                cfg = replace(base, strategy=strategy, keep_fraction=keep,
                              codec=var["codec"], **var["kwargs"])
                t0 = time.time()
                res = run_fcf_simulation(train, test, cfg)
                secs = time.time() - t0
                rps = _rounds_per_sec(train, test, cfg, rounds=time_rounds)
                per_round = _per_round_bytes(cfg, num_items)
                fp32_same = _per_round_bytes(
                    replace(cfg, codec="fp32"), num_items)["total"]
                cells.append({
                    "strategy": strategy, "codec": var["label"],
                    "codec_base": var["codec"], "keep": keep,
                    "topk_fraction": cfg.codec_topk_fraction
                    if var["codec"] == "topk" else None,
                    "int4_error_feedback": cfg.codec_int4_error_feedback,
                    "precision_at_10": res.final["precision"],
                    "f1": res.final["f1"], "map": res.final["map"],
                    "bytes_per_round": per_round,
                    "bytes_down_total": res.bytes_down,
                    "bytes_up_total": res.bytes_up,
                    "rounds_per_sec": rps,
                    "reduction_vs_full_fp32":
                        full_bytes / per_round["total"],
                    "reduction_vs_same_keep_fp32":
                        fp32_same / per_round["total"],
                    "precision_drop_pct_vs_full": 100.0 * (
                        1.0 - res.final["precision"] / max(full_p10, 1e-9)),
                    "f1_drop_pct_vs_full": 100.0 * (
                        1.0 - res.final["f1"] / max(full_f1, 1e-9)),
                    "sim_seconds": secs,
                })

    def cell(strategy, codec, keep):
        for c in cells:
            if (c["strategy"], c["codec"], c["keep"]) == (strategy, codec, keep):
                return c
        return None

    paper_row = cell("bts", "fp32", 0.10)
    int8_row = cell("bts", "int8", 0.10)
    headline = {
        "full_fp32_bytes_per_round": full_bytes,
        "full_fp32_precision_at_10": full_p10,
        "full_fp32_f1": full_f1,
        # the paper's Table-4 row: ~90% payload reduction from selection
        "paper_row_reduction_vs_full": paper_row["reduction_vs_full_fp32"]
        if paper_row else None,
        # the new joint-axis row: selection x int8 quantization
        "int8_bts_reduction_vs_full": int8_row["reduction_vs_full_fp32"]
        if int8_row else None,
        "int8_bts_reduction_vs_same_keep_fp32":
            int8_row["reduction_vs_same_keep_fp32"] if int8_row else None,
        "int8_bts_precision_drop_pct_vs_full":
            int8_row["precision_drop_pct_vs_full"] if int8_row else None,
    }

    out = {
        "dataset": {"name": spec.name, "users": int(train.shape[0]),
                    "items": int(num_items)},
        "config": {"rounds": rounds, "theta": theta,
                   "num_factors": base.num_factors, "seed": seed},
        "headline": headline,
        "cells": cells,
    }

    print(f"\n## Payload compression Pareto — {spec.name} "
          f"(M={num_items}, K={base.num_factors}, Theta={theta}, "
          f"{rounds} rounds; full-fp32: P@10={full_p10:.4f}, "
          f"{full_bytes / 1e3:.1f} KB/round)\n")
    rows = []
    for c in sorted(cells, key=lambda c: -c["reduction_vs_full_fp32"]):
        rows.append((
            c["strategy"], c["codec"], f"{c['keep']:.2f}",
            f"{c['bytes_per_round']['total'] / 1e3:.1f}",
            f"{c['reduction_vs_full_fp32']:.1f}x",
            f"{c['precision_at_10']:.4f}",
            f"{c['precision_drop_pct_vs_full']:+.1f}%",
            f"{c['rounds_per_sec']:.0f}",
        ))
    print(markdown_table(
        ("strategy", "codec", "keep", "KB/round", "vs full fp32",
         "P@10", "P@10 drop", "rounds/s"), rows))
    if paper_row and int8_row:
        print(f"\npaper row (bts, fp32, keep=0.10): "
              f"{paper_row['reduction_vs_full_fp32']:.1f}x fewer bytes "
              f"({100 * (1 - 1 / paper_row['reduction_vs_full_fp32']):.0f}% "
              f"reduction)")
        print(f"int8+BTS  (bts, int8, keep=0.10): "
              f"{int8_row['reduction_vs_full_fp32']:.1f}x fewer bytes, "
              f"P@10 drop {int8_row['precision_drop_pct_vs_full']:+.1f}% "
              f"(target >= 4x)")
        assert int8_row["reduction_vs_full_fp32"] >= 4.0, \
            "int8+BTS must cut bytes/round by >= 4x at matched fraction"

    if out_path:
        with open(out_path, "w") as f:
            json.dump(out, f, indent=1, default=float)
        print(f"\nwrote {out_path}")
    return out


def dry_run() -> Dict:
    """Accounting-only smoke: no simulations, just the byte math."""
    base = FLSimConfig(rounds=1, theta=50)
    num_items = 300
    rows = []
    variants = _variants(CODECS, TOPK_FRACTIONS)
    for var in variants:
        cfg = replace(base, strategy="bts", keep_fraction=0.1,
                      codec=var["codec"], **var["kwargs"])
        b = _per_round_bytes(cfg, num_items)
        rows.append((var["label"], b["down"], b["up"], b["total"]))
    print("\n[dry-run] payload_compression — bytes/round at M=300, "
          "K=25, Theta=50, keep=0.10\n")
    print(markdown_table(("codec", "down B", "up B", "total B"), rows))
    return {"dry_run": True, "cells_planned":
            len(STRATEGIES) * len(variants) * len(KEEPS) + 1}


def main(argv: Optional[Sequence[str]] = None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="movielens-mini")
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--quick", action="store_true",
                    help="fewer cells / rounds for smoke runs")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the planned grid + byte math, run nothing")
    args = ap.parse_args(argv)
    if args.dry_run:
        return dry_run()
    if args.quick:
        return run(dataset=args.dataset, rounds=40, theta=20,
                   strategies=("bts",), keeps=(0.10,), time_rounds=20,
                   out_path=None)
    return run(dataset=args.dataset, rounds=args.rounds)


if __name__ == "__main__":
    main()
