"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 -m bench.calibrate --workload <cell> --seeds 1,2,... \
        [--controls 3] [--rates 100,200,...] [--seconds 4]

In one process on the chip, for a cell at its own size:

  program   the numbers that decide ``correct``, on every seed: the lower
            readings
  control   the next precision down, on the first ``--controls`` seeds:
            the upper readings. Training: the reference computed in
            bfloat16, put in the program's place. Serving: the program's
            own int4 path (an engine over the same table sent as int4),
            serving the same largest request
  faults    training: half of each cohort left out with the mean taken over
            the rest; serving: one served id altered where it is produced.
            A state left unchanged reads 1 by change_gap's measure.

``--rates`` (serving) instead sweeps the offered load: p50/p95 and the
backlog at the close of a ``--seconds`` window at each rate. Prints one
JSON object as its last line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench.run import log, start


def train_readings(jax, cell, seeds, n_controls, counter) -> dict:
    import jax.numpy as jnp

    from bench.harness import compare, reference, train

    train_j, test_j = train.device_data(jax, cell.config)
    rcfg = reference.ref_round_config(cell.config, cell.traffic,
                                      cell.config["data"]["num_items"])
    rounds = cell.config["eval"]["every"]
    out = {"program": {}, "control": {}, "half_cohort": {}, "unchanged": {}}
    for seed in seeds:
        t0 = time.perf_counter()
        run = train.drive(jax, cell, seed, 0.0, False, counter, train_j,
                          test_j)
        numbers, leaves = train.check(jax, cell, seed, run.first_state,
                                      train_j, leaves=True)
        out["program"][seed] = dict(numbers, leaves=leaves)
        log(f"seed {seed}: {out['program'][seed]} "
            f"({time.perf_counter() - t0:.1f} s)")
    for seed in seeds[:n_controls]:
        ref = reference.run_training(rcfg, train_j, seed, rounds)
        for key, kw in (("control", {"dtype": jnp.bfloat16}),
                        ("half_cohort", {"fault": "half_cohort"}),
                        ("unchanged", {"fault": "unchanged"})):
            alt = reference.run_training(rcfg, train_j, seed, rounds, **kw)
            out[key][seed] = dict(
                compare.training_numbers(alt, ref, rcfg.strategy),
                leaves=compare.leaf_gaps(alt, ref, rcfg.strategy))
            log(f"seed {seed} {key}: {out[key][seed]}")
    return out


def serve_readings(jax, cell, seeds, n_controls, seconds) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from bench.harness import compare, reference, serve

    mix = cell.traffic
    out = {"program": {}, "control": {}, "altered_answer": {}}
    for i, seed in enumerate(seeds):
        engine, table, p, train_u8 = serve.build(jax, cell, seed)
        sched = serve.schedule(mix, cell.config["data"]["num_users"], seed,
                               seconds)
        users_p = np.ascontiguousarray(p[sched.order])
        seen = np.ascontiguousarray(train_u8[sched.order], dtype=np.float32)
        serve.warm(engine, [r.size for r in sched.requests], users_p, seen,
                   jax, mix["top_n"])
        served = serve.play(engine, sched, users_p, seen, mix["workers"],
                            jax, mix["top_n"])
        numbers, n_req, n_users = serve.check(
            table, users_p, seen, served, seed, mix["top_n"],
            mix["check_sample"])
        out["program"][seed] = numbers
        log(f"seed {seed}: {numbers} over {n_req} requests, {n_users} users")
        if i >= n_controls:
            continue
        ok = [s for s in served if s.error is None]
        big = max(ok, key=lambda s: s.req.size)
        rows = slice(big.req.start, big.req.start + big.req.size)
        pu, su = jnp.asarray(users_p[rows]), jnp.asarray(seen[rows])
        ref_s, ref_i = reference.topn(table, pu, su, mix["top_n"])
        low = serve.engine_for(table, "int4", mix)
        low_v, low_i = low.recommend(users_p[rows], top_n=mix["top_n"],
                                     train_mask=seen[rows])
        out["control"][seed] = compare.serving_numbers(
            np.asarray(low_v), np.asarray(low_i), np.asarray(ref_s),
            np.asarray(ref_i))
        ids = np.asarray(big.ids).copy()
        ids[0, 0] = (ids[0, 0] + 1) % cell.config["data"]["num_items"]
        out["altered_answer"][seed] = compare.serving_numbers(
            np.asarray(big.scores), ids, np.asarray(ref_s),
            np.asarray(ref_i))
        log(f"seed {seed} control {out['control'][seed]} altered "
            f"{out['altered_answer'][seed]}")
    return out


def sweep(jax, cell, seed, rates, seconds) -> dict:
    import numpy as np

    from bench.harness import serve, stats

    mix = cell.traffic
    engine, table, p, train_u8 = serve.build(jax, cell, seed)
    warmed = False
    out = {}
    for rate in rates:
        m = dict(mix, rate_per_s=rate)
        sched = serve.schedule(m, cell.config["data"]["num_users"], seed,
                               seconds)
        users_p = np.ascontiguousarray(p[sched.order])
        seen = np.ascontiguousarray(train_u8[sched.order], dtype=np.float32)
        serve.warm(engine, [r.size for r in sched.requests], users_p, seen,
                   jax, mix["top_n"])
        if not warmed:
            burst = serve.schedule(mix, cell.config["data"]["num_users"],
                                   seed + 1, float(mix["warm_seconds"]))
            serve.play(engine, serve.Schedule(burst.requests, sched.order),
                       users_p, seen, mix["workers"], jax, mix["top_n"])
            warmed = True
        served = serve.play(engine, sched, users_p, seen, mix["workers"],
                            jax, mix["top_n"])
        lat = serve.latencies(served)
        third = len(served) // 3
        q = lat["queue_ms"]
        out[rate] = {
            "requests": len(served),
            "p50_ms": stats.percentile(lat["total_ms"], 50),
            "p95_ms": stats.percentile(lat["total_ms"], 95),
            "queue_first_third_ms": float(np.mean(q[:third])),
            "queue_last_third_ms": float(np.mean(q[-third:])),
            "backlog_at_close": sum(1 for s in served
                                    if s.entered > seconds),
            "late_p95_ms": stats.percentile(lat["late_ms"], 95),
            "worst_per_second_ms": serve.timeline(served, seconds)}
        log(f"rate {rate}: {out[rate]}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=4.0)
    a = ap.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",")]
    args, cell, jax, devices, counter = start(
        ["--workload", a.workload, "--seed", str(seeds[0]),
         "--seconds", str(a.seconds)])
    kind = cell.traffic["kind"]
    if a.rates:
        res = sweep(jax, cell, seeds[0],
                    [float(r) for r in a.rates.split(",")], a.seconds)
    elif kind == "train":
        res = train_readings(jax, cell, seeds, a.controls, counter)
    else:
        res = serve_readings(jax, cell, seeds, a.controls, a.seconds)
    print(json.dumps({"workload": cell.name, "readings": res}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
