"""FRS serving driver: train briefly, publish encoded snapshots into a
:class:`repro.serve.ServingEngine`, then serve batched recommendation
requests straight off the compressed model.

The full deployment loop of the paper's system in one command: the async
round engine publishes its encoded Q* ring entries at every eval boundary
(``FLSimConfig.snapshot_hook``), the engine installs them into the
wire-resident serving model WITHOUT a fp32 round-trip, and a request
stream of per-user factor vectors is scored through the fused
dequant->score->top-N kernel (:mod:`repro.kernels.payload_score`).

  PYTHONPATH=src python -m repro.launch.serve_recs --codec int8 \
      --rounds 60 --requests 200 --batch 32

See also :mod:`repro.launch.serve` for the LLM decode serving driver.
"""
from __future__ import annotations

import argparse
import os
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress import CodecConfig
from repro.data.synthetic import load_dataset
from repro.federated.simulation import FLSimConfig, run_fcf_simulation
from repro.obs import JsonlSink, LatencyHistogram, ObsConfig
from repro.obs.trace import install_tracer
from repro.serve import ServingEngine, ServingModel
from repro.utils.logging import get_logger

log = get_logger("repro.serve_recs")


def _build_obs(args) -> Optional[ObsConfig]:
    """An enabled ObsConfig when observability is asked for, else None.

    ``--obs-out DIR`` turns the full stream on: round telemetry to
    ``DIR/telemetry.jsonl``, host spans to ``DIR/trace.jsonl``, and a final
    ``DIR/metrics.prom`` scrape — the exact artifact set
    ``python -m repro.obs.check DIR`` validates. ``--metrics-port`` alone
    still enables in-loop telemetry (in-memory sink) so the live endpoint
    has latency histograms to serve.
    """
    if args.obs_out is None and args.metrics_port < 0:
        return None
    if args.obs_out is None:
        return ObsConfig(enabled=True, telemetry_every=args.telemetry_every)
    os.makedirs(args.obs_out, exist_ok=True)
    return ObsConfig(
        enabled=True,
        telemetry_every=args.telemetry_every,
        sink=JsonlSink(os.path.join(args.obs_out, "telemetry.jsonl")),
        trace_path=os.path.join(args.obs_out, "trace.jsonl"),
    )


def serve_recs(args) -> dict:
    spec, train, test = load_dataset(args.dataset, seed=args.seed)
    m = train.shape[1]
    k = args.factors
    obs = _build_obs(args)

    # cold engine around an all-zero wire model; training will publish into
    # it (the first published snapshot is the first real serving model)
    engine = ServingEngine(
        ServingModel.from_dense(CodecConfig(name=args.codec),
                                jnp.zeros((m, k), jnp.float32)),
        buckets=tuple(args.buckets), top_n=args.top_n,
        block_m=args.block_m, obs=obs)

    cfg = FLSimConfig(
        strategy="bts", rounds=args.rounds, theta=args.theta,
        num_factors=k, codec=args.codec, backend="async",
        max_staleness=args.max_staleness, eval_every=args.eval_every,
        eval_users=min(128, train.shape[0]), seed=args.seed,
        snapshot_hook=engine.publisher(), obs=obs)
    prev_tracer = None
    tracer_installed = False
    if obs is not None and obs.resolve_tracer() is not None:
        # keep the tracer installed past training so the serving phase's
        # serve_batch / publish spans land in the same trace.jsonl
        prev_tracer = install_tracer(obs.resolve_tracer())
        tracer_installed = True
    t0 = time.time()
    result = run_fcf_simulation(train, test, cfg)
    t_train = time.time() - t0
    log.info("trained %d rounds in %.2fs (F1@10 %.4f), published %d "
             "snapshots, serving model: %s wire, %d bytes resident",
             result.rounds, t_train, result.final["f1"],
             engine.stats().installs, engine.model.cfg.name,
             engine.model.resident_bytes())

    # request stream: solve eval users' factors once (the client-side step),
    # then serve them in random batches against the live engine
    from repro.cf.local import solve_user_factors

    q_dense = jnp.asarray(result.server_state.q)
    rng = np.random.default_rng(args.seed + 7)
    users = rng.choice(train.shape[0],
                       size=min(256, train.shape[0]), replace=False)
    p_all = solve_user_factors(q_dense, jnp.asarray(train[users]))
    mask_all = jnp.asarray(train[users])

    lat: List[float] = []
    for r in range(args.requests):
        ids = rng.integers(0, p_all.shape[0], size=args.batch)
        pb = p_all[ids]
        mb = mask_all[ids] if args.mask_train else None
        t0 = time.time()
        vals, idx = engine.recommend(pb, train_mask=mb)
        jax.block_until_ready(idx)
        lat.append(time.time() - t0)
    lat_arr = np.asarray(lat[1:]) if len(lat) > 1 else np.asarray(lat)
    users_per_s = args.batch * len(lat_arr) / max(lat_arr.sum(), 1e-9)
    stats = engine.stats()
    # one quantile definition repo-wide (obs.hist): this summary, the
    # engine's /metrics histograms and benchmarks/serving.py all read
    # p50/p99 off the same geometric bucketing
    req_hist = LatencyHistogram.from_values(lat_arr)
    summary = {
        "dataset": spec.name, "codec": args.codec, "batch": args.batch,
        "requests": stats.requests, "users_served": stats.users,
        "model_version": stats.version,
        "resident_bytes": engine.model.resident_bytes(),
        "users_per_sec": float(users_per_s),
        "p50_ms": req_hist.quantile(0.50) * 1e3,
        "p99_ms": req_hist.quantile(0.99) * 1e3,
        "f1_at_10": result.final["f1"],
    }
    log.info("served %d requests x %d users: %.0f users/s, "
             "p50 %.2f ms, p99 %.2f ms",
             stats.requests, args.batch, summary["users_per_sec"],
             summary["p50_ms"], summary["p99_ms"])

    server = None
    try:
        if args.metrics_port >= 0:
            from repro.obs.httpd import start_metrics_server
            server, url = start_metrics_server(engine.metrics,
                                               port=args.metrics_port)
            summary["metrics_url"] = url
            import urllib.request
            with urllib.request.urlopen(url, timeout=10) as resp:
                scraped = resp.read().decode("utf-8")
            log.info("metrics endpoint live at %s (%d bytes/scrape)",
                     url, len(scraped))
            if not args.serve_forever:
                pass    # CI mode: scrape once to prove liveness, then stop
            else:
                log.info("serving /metrics until interrupted (ctrl-c)")
                try:
                    while True:
                        time.sleep(3600)
                except KeyboardInterrupt:
                    pass
        if args.obs_out is not None:
            prom_path = os.path.join(args.obs_out, "metrics.prom")
            with open(prom_path, "w") as f:
                f.write(engine.metrics())
            summary["obs_out"] = args.obs_out
            log.info("observability artifacts in %s "
                     "(telemetry.jsonl, trace.jsonl, metrics.prom)",
                     args.obs_out)
    finally:
        if server is not None:
            server.shutdown()
        if tracer_installed:
            install_tracer(prev_tracer)
        if obs is not None:
            obs.close()
    return summary


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dataset", default="movielens-mini")
    ap.add_argument("--codec", default="int8",
                    choices=("fp32", "fp16", "int8", "int4"))
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--theta", type=int, default=50)
    ap.add_argument("--factors", type=int, default=25)
    ap.add_argument("--max-staleness", type=int, default=2)
    ap.add_argument("--eval-every", type=int, default=20)
    ap.add_argument("--requests", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--top-n", type=int, default=10)
    ap.add_argument("--block-m", type=int, default=1024)
    ap.add_argument("--buckets", type=int, nargs="+", default=[8, 64, 256])
    ap.add_argument("--mask-train", action="store_true",
                    help="exclude each user's train interactions")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--obs-out", default=None, metavar="DIR",
                    help="enable observability and write telemetry.jsonl / "
                         "trace.jsonl / metrics.prom into DIR (validate "
                         "with: python -m repro.obs.check DIR)")
    ap.add_argument("--telemetry-every", type=int, default=1,
                    help="emit a round-telemetry event every N rounds")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="serve Prometheus /metrics on this port "
                         "(0 = ephemeral, -1 = off)")
    ap.add_argument("--serve-forever", action="store_true",
                    help="with --metrics-port: keep the endpoint up until "
                         "interrupted instead of one liveness scrape")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny smoke config (seconds, CI-sized)")
    return ap


def main(argv: Optional[List[str]] = None) -> dict:
    args = build_parser().parse_args(argv)
    if args.dry_run:
        args.rounds, args.eval_every = 6, 3
        args.requests, args.batch = 4, 4
        args.buckets, args.block_m = [4], 128
    out = serve_recs(args)
    print(f"serve_recs: {out['users_per_sec']:.0f} users/s "
          f"(p50 {out['p50_ms']:.2f} ms, p99 {out['p99_ms']:.2f} ms) "
          f"on a {out['codec']} wire model, "
          f"{out['resident_bytes']} bytes resident, "
          f"model v{out['model_version']}")
    return out


if __name__ == "__main__":
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
