"""Open-loop recommendation requests against ``ServingEngine.recommend``.

The schedule is drawn from the seed before the window: every seed gets the
same multiset of request sizes and inter-arrival gaps, in its own order, and
its own users. Arrivals are Poisson at the mix's fixed rate (the gaps are
the exponential distribution's quantiles, shuffled). A share of requests
carries one user (an app opening a user's page); the rest carry B users,
B uniform over the mix's range (a gateway asking for a page of users). Each
request hands over its users' factors and seen-item rows as host float32
arrays, as a front end would.

A generator thread releases each request at its due time whether or not
earlier ones have finished; a fixed pool of worker threads calls
``recommend`` and waits until the result is ready. Latency counts from the
due time. After the window every sampled answer is compared with the plain
reference (``bench/harness/reference.py``).
"""
from __future__ import annotations

import queue
import shutil
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from bench.harness import compare, stats
from bench.harness.device import CACHE_DIR


class Request(NamedTuple):
    due: float            # seconds after the window opens
    start: int            # first row of the request's users in the order
    size: int             # users in the request


class Schedule(NamedTuple):
    requests: List[Request]
    order: np.ndarray     # (users,) the seed's permutation of the users


def schedule(mix: dict, num_users: int, seed: int, seconds: float
             ) -> Schedule:
    """Requests due in ``[0, seconds)`` at the mix's rate."""
    rng = np.random.default_rng(seed)
    rate = float(mix["rate_per_s"])
    n = int(np.ceil(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = gaps * (seconds / gaps.sum())
    rng.shuffle(gaps)
    n_multi = int(round(n * (1.0 - mix["single_share"])))
    lo, hi = mix["batch_min"], mix["batch_max"]
    multi = lo + (np.arange(n_multi) * (hi - lo + 1)) // max(n_multi, 1)
    sizes = np.concatenate([np.ones(n - n_multi, np.int64), multi])
    rng.shuffle(sizes)
    order = rng.permutation(num_users)
    starts = rng.integers(0, num_users - hi, size=n)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    reqs = [Request(float(d), int(s), int(b))
            for d, s, b in zip(due, starts, sizes)]
    return Schedule(reqs, order)


class Served(NamedTuple):
    req: Request
    released: float       # when the generator handed it over (window clock)
    entered: float        # when a worker called recommend
    ready: float          # when the result was ready
    scores: object        # device arrays, read back after the window
    ids: object
    error: Optional[str]


def play(engine, sched: Schedule, users_p: np.ndarray, seen: np.ndarray,
         workers: int, jax, top_n: int, t0: Optional[float] = None
         ) -> List[Served]:
    """Release every request at ``t0 + due`` and serve it; returns once all
    have finished. ``users_p``/``seen`` are already in the schedule's user
    order, so each request's rows are a contiguous slice (no copy)."""
    todo: "queue.Queue" = queue.Queue()
    out: List[Served] = []
    lock = threading.Lock()
    t0 = time.perf_counter() if t0 is None else t0

    def work():
        while True:
            item = todo.get()
            if item is None:
                return
            req, released = item
            p = users_p[req.start:req.start + req.size]
            mask = seen[req.start:req.start + req.size]
            entered = time.perf_counter() - t0
            err = None
            vals = ids = None
            try:
                vals, ids = engine.recommend(p, top_n=top_n, train_mask=mask)
                jax.block_until_ready((vals, ids))
            except Exception as e:      # noqa: BLE001 — counted as failed
                err = f"{type(e).__name__}: {e}"
            ready = time.perf_counter() - t0
            with lock:
                out.append(Served(req, released, entered, ready, vals, ids,
                                  err))

    threads = [threading.Thread(target=work, daemon=True)
               for _ in range(workers)]
    for t in threads:
        t.start()
    for req in sched.requests:
        delay = t0 + req.due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        todo.put((req, time.perf_counter() - t0))
    for _ in threads:
        todo.put(None)
    for t in threads:
        t.join()
    return sorted(out, key=lambda s: s.req.due)


def latencies(served: List[Served]) -> Dict[str, list]:
    ok = [s for s in served if s.error is None]
    return {
        "total_ms": [1e3 * (s.ready - s.req.due) for s in ok],
        "queue_ms": [1e3 * (s.entered - s.req.due) for s in ok],
        "service_ms": [1e3 * (s.ready - s.entered) for s in ok],
        "late_ms": [1e3 * (s.released - s.req.due) for s in served],
    }


def timeline(served: List[Served], seconds: float) -> List[float]:
    """The worst latency, due to ready, of the requests due in each second
    of the window: where a stall or a backlog sat."""
    worst = [0.0] * int(np.ceil(seconds))
    for s in served:
        if s.error is None:
            i = min(int(s.req.due), len(worst) - 1)
            worst[i] = max(worst[i], round(1e3 * (s.ready - s.req.due), 1))
    return worst


def engine_for(table, wire: str, mix: dict):
    """The program's serving engine over ``table`` sent as ``wire``."""
    from repro.compress import CodecConfig
    from repro.serve import ServingEngine, ServingModel

    return ServingEngine(
        ServingModel.from_dense(CodecConfig(name=wire), table),
        buckets=tuple(mix["buckets"]), top_n=mix["top_n"],
        block_m=mix["block_m"])


def build(jax, cell, seed: int):
    """The engine over a seeded int8 table, the users' factors from the
    program's own solve, and the seen-item rows (the whole table, host
    uint8). Dense configurations only."""
    import jax.numpy as jnp

    from repro.cf.local import solve_user_factors

    from bench.harness import data

    cfg, mix = cell.config, cell.traffic
    if data.layout(cfg["data"]) != "dense":
        raise ValueError(
            f"the serving driver takes a dense configuration only; "
            f"{cfg['name']} states layout {data.layout(cfg['data'])!r}, "
            f"whose seen-item rows it cannot hold whole")
    m, k = cfg["data"]["num_items"], cfg["num_factors"]
    train, _ = data.dataset(cfg["data"], CACHE_DIR / "data")
    scale = float(mix["table_scale"])
    table = jax.jit(lambda key: scale * jax.random.normal(
        key, (m, k), jnp.float32))(jax.random.PRNGKey(seed))
    engine = engine_for(table, cfg["wire"], mix)
    seen_dev = jax.jit(lambda a: a.astype(jnp.float32))(jnp.asarray(train))
    p = np.asarray(solve_user_factors(table, seen_dev, l2=cfg["l2"],
                                      alpha=cfg["alpha"]))
    return engine, table, p, train


def warm(engine, sizes, users_p, seen, jax, top_n: int) -> None:
    """One request of every size the traffic uses: each padded bucket, and
    the per-size pad programs in front of it."""
    for b in sorted(set(sizes)):
        jax.block_until_ready(engine.recommend(
            users_p[:b], top_n=top_n, train_mask=seen[:b]))


def check(table, users_p, seen, served: List[Served], seed: int, top_n: int,
          sample: int, block: int = 128):
    """Compare a seeded sample of answers, the largest request among them,
    with the reference's top-N of the same users (scored in fixed blocks of
    users, so the reference compiles once). Returns the worst numbers, the
    requests compared and their users."""
    import jax.numpy as jnp

    from bench.harness import reference

    ok = [s for s in served if s.error is None]
    rng = np.random.default_rng(seed + 1)
    largest = max(range(len(ok)), key=lambda i: ok[i].req.size)
    pick = sorted(set(rng.choice(len(ok), size=min(sample, len(ok)),
                                 replace=False).tolist()) | {largest})
    rows = np.concatenate([np.arange(ok[i].req.start,
                                     ok[i].req.start + ok[i].req.size)
                           for i in pick])
    n = len(rows)
    padded = np.pad(rows, (0, -n % block))
    ref_s, ref_i = [], []
    for b0 in range(0, len(padded), block):
        r = padded[b0:b0 + block]
        s_blk, i_blk = reference.topn(table, jnp.asarray(users_p[r]),
                                      jnp.asarray(seen[r]), top_n)
        ref_s.append(np.asarray(s_blk))
        ref_i.append(np.asarray(i_blk))
    ref_s = np.concatenate(ref_s)[:n]
    ref_i = np.concatenate(ref_i)[:n]
    served_s = np.concatenate([np.asarray(ok[i].scores) for i in pick])
    served_i = np.concatenate([np.asarray(ok[i].ids) for i in pick])
    numbers = compare.serving_numbers(served_s, served_i, ref_s, ref_i)
    return numbers, len(pick), n


def run(args, cell, jax, devices, counter, process_start, log) -> dict:
    from types import SimpleNamespace

    from bench.harness import device, spec, trace as trace_mod
    from bench.harness.device import ROOT

    cfg, mix = cell.config, cell.traffic
    engine, table, p, train = build(jax, cell, args.seed)
    trace_s = float(mix["trace_seconds"]) if args.trace else 0.0
    sched = schedule(mix, cfg["data"]["num_users"], args.seed,
                     args.seconds + trace_s)
    users_p = np.ascontiguousarray(p[sched.order])
    seen = np.ascontiguousarray(train[sched.order], dtype=np.float32)
    warm(engine, [r.size for r in sched.requests], users_p, seen, jax,
         mix["top_n"])
    # a short stretch of the same traffic, served and dropped: what the
    # runtime grows under concurrent requests grows here, not in the window
    burst = schedule(mix, cfg["data"]["num_users"], args.seed + 1,
                     float(mix["warm_seconds"]))
    play(engine, Schedule(burst.requests, sched.order), users_p, seen,
         mix["workers"], jax, mix["top_n"])
    window_gc = device.WindowGc()
    window_gc.open()
    setup_s = time.perf_counter() - process_start
    compiles_before = counter.count
    log(f"set-up {setup_s:.3f} s; {counter.describe()}")

    main = Schedule([r for r in sched.requests if r.due < args.seconds],
                    sched.order)
    try:
        served = play(engine, main, users_p, seen, mix["workers"], jax,
                      mix["top_n"])
    finally:
        window_gc.close()
    lat = latencies(served)
    failed = sum(s.error is not None for s in served)
    in_window = counter.count - compiles_before
    backlog = sum(1 for s in served if s.entered > args.seconds)
    log(f"window: {len(served)} requests due in {args.seconds} s, "
        f"{failed} failed, {in_window} compilations inside the window, "
        f"{backlog} still queued at the close; {window_gc.describe()}")
    if lat["late_ms"]:
        log(f"generator lateness p50 {stats.percentile(lat['late_ms'], 50)!r}"
            f" ms, p95 {stats.percentile(lat['late_ms'], 95)!r} ms, max "
            f"{max(lat['late_ms'])!r} ms")
    log(f"worst latency in each second of the window (ms): "
        f"{timeline(served, args.seconds)}")

    summary = None
    if args.trace:
        tdir = str(CACHE_DIR / "trace" / cell.name)
        shutil.rmtree(tdir, ignore_errors=True)
        rest = Schedule([r._replace(due=r.due - args.seconds)
                         for r in sched.requests if r.due >= args.seconds],
                        sched.order)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            play(engine, rest, users_p, seen, mix["workers"], jax,
                 mix["top_n"])
        jax.profiler.stop_trace()
        summary = trace_mod.load(trace_mod.find_xplane(tdir),
                                 chips=len(devices))

    memory = device.memory_peak_bytes(devices)
    t_ref = time.perf_counter()
    numbers, n_req, n_users = check(table, users_p, seen, served, args.seed,
                                    mix["top_n"], mix["check_sample"])
    log(f"reference comparison of {n_req} requests ({n_users} users) took "
        f"{time.perf_counter() - t_ref:.3f} s")
    verdict = compare.judge(numbers, compare.load_limits(ROOT, cell.name))
    dev = dict(device.describe(devices), memory_peak_bytes=memory)
    out = {"correct": verdict["ok"] and failed == 0 and in_window == 0,
           "attempted": len(served), "failed": failed,
           "checks": verdict["checks"], "device": dev}
    if not args.trace:
        values = {"setup_s": setup_s,
                  "serve_p50_ms": stats.percentile(lat["total_ms"], 50),
                  "serve_p95_ms": stats.percentile(lat["total_ms"], 95)}
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end if m["name"] in values}
        return out
    ok = [s for s in served if s.error is None]
    m, k = cfg["data"]["num_items"], cfg["num_factors"]
    ctx = SimpleNamespace(
        cell=cell, device_kind=devices[0].device_kind, summary=summary,
        latencies=lat, num_items=m, k=k, top_n=mix["top_n"],
        useful_ops=sum(2.0 * s.req.size * m * k for s in ok),
        service_s=sum(s.ready - s.entered for s in ok))
    out["metrics"] = spec.read_per_layer(cell, ctx)
    dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
    out["breakdown"] = {"device_ops": summary.top_ops(),
                        "idle_gaps": summary.top_gaps()}
    return out
