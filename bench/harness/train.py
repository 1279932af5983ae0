"""Closed-loop federated training rounds through the program's public loop.

One ``run_fcf_simulation(backend="scan")`` call is the object under test:
it compiles one chunk program of ``eval_every`` rounds, and between chunks
runs eval and then ``snapshot_hook``. The hook is the harness's wrapper
around ``ServingEngine.publisher()``: it publishes, waits until the
installed model is ready, and stamps the chunk boundary.

  set-up   process start to the first boundary: data, compile (or cache
           load) of the chunk, eval and publish programs, and the first
           chunk, whose end state the correctness comparison keeps
  window   from the first boundary to the last boundary within
           ``--seconds`` (a boundary is the last one when the next is
           predicted, from the chunk just done, to fall past the limit)
  trace    with ``--trace 1`` a further ``trace_chunks`` chunks run under
           the profiler after the window

The loop is stopped from the hook by :class:`WindowClosed`, a
``BaseException`` that the loop's ``except Exception`` lets through; the
round cap ``max_rounds`` of the mix only bounds the pre-sampled cohorts.
"""
from __future__ import annotations

import shutil
import time
from typing import Any, Dict, List, NamedTuple, Optional

from bench.harness import compare, counts, device
from bench.harness.device import CACHE_DIR


class WindowClosed(BaseException):
    """Raised from the snapshot hook to end the training loop."""


class Boundary(NamedTuple):
    round: int
    t: float              # perf_counter when the published model was ready
    publish_s: float      # the publisher call, until the model was ready
    compiles: int         # compilations seen by then


class TrainRun(NamedTuple):
    setup_end: float
    boundaries: List[Boundary]
    window: tuple         # (first, last) boundary index of the window
    traced: Optional[tuple]
    trace_dir: Optional[str]
    first_state: Any
    last_state: Any
    engine: Any
    publish_failures: int
    hook_failures: int
    gc: str               # the collector's passes while the window was open


class _Hook:
    def __init__(self, jax, engine, seconds, trace_chunks, trace_dir,
                 counter, window_gc):
        self.jax = jax
        self.window_gc = window_gc
        self.engine = engine
        self.publish = engine.publisher()
        self.seconds = seconds
        self.trace_chunks = trace_chunks
        self.trace_dir = trace_dir
        self.counter = counter
        self.marks: List[Boundary] = []
        self.first_state = None
        self.last_state = None
        self.window_end: Optional[int] = None
        self.trace_start: Optional[int] = None
        self.trace_end: Optional[int] = None
        self._annotation = None

    def __call__(self, round_: int, state) -> None:
        jax = self.jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.publish"):
            self.publish(round_, state)
            jax.block_until_ready(self.engine.model.wire)
        if not self.marks:
            self.window_gc.open()
        t1 = time.perf_counter()
        self.marks.append(Boundary(round_, t1, t1 - t0, self.counter.count))
        i = len(self.marks) - 1
        if i == 0:
            self.first_state = state
            return
        if self.window_end is None:
            self.last_state = state
            elapsed = t1 - self.marks[0].t
            if elapsed + (t1 - self.marks[i - 1].t) <= self.seconds:
                return
            self.window_end = i
            if not self.trace_chunks:
                raise WindowClosed()
            self._start_trace(i)
            return
        if i - self.trace_start >= self.trace_chunks:
            self._stop_trace(i)
            raise WindowClosed()

    def _start_trace(self, i: int) -> None:
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._annotation = self.jax.profiler.TraceAnnotation("bench.window")
        self._annotation.__enter__()
        self.trace_start = i

    def _stop_trace(self, i: int) -> None:
        self._annotation.__exit__(None, None, None)
        self.jax.profiler.stop_trace()
        self.trace_end = i


def sim_config(cfg: dict, mix: dict, seed: int, hook):
    """The program's ``FLSimConfig`` for this configuration and mix."""
    from repro.federated import FLSimConfig

    tr, opt, bandit = mix["training"], cfg["server_adam"], cfg["bandit"]
    if opt["beta2"] != bandit["reward_beta2"]:
        raise ValueError("the program shares one beta2 between Adam and the "
                         "reward EMA; the configuration must too")
    return FLSimConfig(
        backend="scan", strategy=tr["strategy"],
        keep_fraction=tr["keep_fraction"], rounds=mix["max_rounds"],
        theta=cfg["theta"], num_factors=cfg["num_factors"], l2=cfg["l2"],
        alpha=cfg["alpha"], lr=opt["lr"], beta1=opt["beta1"],
        beta2=opt["beta2"], gamma=bandit["gamma"],
        mu_theta=bandit["mu_theta"], tau_theta=bandit["tau_theta"],
        reward_mode=bandit["reward_mode"],
        reward_feedback=bandit["reward_feedback"],
        reward_norm=bandit["reward_norm"], codec=cfg["wire"],
        moment_m_dtype=cfg["moments"], moment_v_dtype=cfg["moments"],
        eval_every=cfg["eval"]["every"], eval_users=cfg["eval"]["users"],
        snapshot_hook=hook, seed=seed)


def device_data(jax, cfg: dict):
    """The configuration's train and test interactions as the program takes
    them: (users, items) float32 matrices on the chip, or, for a ``lists``
    layout, a CSR triple ``(indptr, indices, (users, items))`` of host
    NumPy arrays per split, which the program lays out on the chip in its
    own set-up (``bench/README.md``)."""
    import jax.numpy as jnp

    from bench.harness import data

    train, test = data.dataset(cfg["data"], CACHE_DIR / "data")
    if data.layout(cfg["data"]) == "lists":
        return train, test
    to_f32 = jax.jit(lambda a: a.astype(jnp.float32))
    return to_f32(jnp.asarray(train)), to_f32(jnp.asarray(test))


def drive(jax, cell, seed: int, seconds: float, trace: bool, counter,
          train_j, test_j) -> TrainRun:
    import jax.numpy as jnp

    from repro.compress import CodecConfig
    from repro.federated import run_fcf_simulation
    from repro.serve import ServingEngine, ServingModel

    cfg, mix = cell.config, cell.traffic
    m, k = cfg["data"]["num_items"], cfg["num_factors"]
    engine = ServingEngine(ServingModel.from_dense(
        CodecConfig(name=cfg["wire"]), jnp.zeros((m, k), jnp.float32)))
    trace_dir = None
    if trace:
        trace_dir = str(CACHE_DIR / "trace" / cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    window_gc = device.WindowGc()
    hook = _Hook(jax, engine, seconds, mix["trace_chunks"] if trace else 0,
                 trace_dir, counter, window_gc)
    res = None
    try:
        res = run_fcf_simulation(train_j, test_j,
                                 sim_config(cfg, mix, seed, hook))
    except WindowClosed:
        pass
    finally:
        window_gc.close()
    marks = hook.marks
    if len(marks) < 2:
        raise RuntimeError("the loop ended before a window could close")
    end = hook.window_end if hook.window_end is not None else len(marks) - 1
    traced = None
    if hook.trace_end is not None:
        traced = (hook.trace_start, hook.trace_end)
    return TrainRun(
        setup_end=marks[0].t, boundaries=marks, window=(0, end),
        traced=traced, trace_dir=trace_dir, first_state=hook.first_state,
        last_state=hook.last_state, engine=engine,
        publish_failures=engine.stats().publish_failures,
        hook_failures=0 if res is None else res.hook_failures,
        gc=window_gc.describe())


def window_numbers(run: TrainRun) -> Dict[str, float]:
    a, b = run.window
    first, last = run.boundaries[a], run.boundaries[b]
    pubs = [x.publish_s for x in run.boundaries[a + 1:b + 1]]
    return {
        "rounds": last.round - first.round,
        "window_s": last.t - first.t,
        "rounds_per_s": (last.round - first.round) / (last.t - first.t),
        "compiles_in_window": last.compiles - first.compiles,
        "publish_s": pubs,
    }


def precision_at_10(engine, state, train_j, test_j, users: int = 512,
                    seed: int = 0) -> float:
    """P@10 of the model the engine serves at the window's end, over a fixed
    sample of users: the share of each user's top 10 unseen items that are
    in the user's held-out test items (a count for the record, not a
    metric). The sampled users' rows come from the matrices, or are built
    from the lists."""
    import jax.numpy as jnp
    import numpy as np

    from repro.cf.local import solve_user_factors

    from bench.harness import data

    n = train_j.shape[0]
    rng = np.random.default_rng(seed)
    ids = np.sort(rng.choice(n, min(users, n), replace=False))
    if isinstance(train_j, data.CSR):
        seen = jnp.asarray(data.rows(train_j, ids))
        test = data.rows(test_j, ids)
    else:
        seen = train_j[jnp.asarray(ids)]
        test = np.asarray(test_j[jnp.asarray(ids)])
    p = solve_user_factors(state.q, seen)
    _, top = engine.recommend(p, top_n=10, train_mask=seen)
    hits = np.take_along_axis(test, np.asarray(top), axis=1)
    return float(hits.sum() / hits.size)


def program_state(state) -> Dict[str, Any]:
    """The leaves of the program's ``ServerState`` that the comparison reads,
    by their public field names."""
    import numpy as np

    out = {"q": state.q, "m": state.opt.m, "v": state.opt.v,
           "t_rows": state.opt.t}
    sel = state.sel
    if hasattr(sel, "bts"):
        out.update(reward_sum=sel.bts.reward_sum, counts=sel.bts.counts,
                   reward_v=sel.reward.v, prev_grad=sel.reward.prev_grad)
    return {key: np.asarray(val) for key, val in out.items()}


def check(jax, cell, seed: int, first_state, train_j, leaves: bool = False):
    """Numbers comparing the program's state after the first chunk with the
    reference after as many rounds from the same seed (and, with
    ``leaves``, each leaf's norm gap too)."""
    from bench.harness import reference

    cfg, mix = cell.config, cell.traffic
    prog = program_state(first_state)
    rcfg = reference.ref_round_config(cfg, mix, cfg["data"]["num_items"])
    ref = reference.run_training(rcfg, train_j, seed,
                                 rounds=cfg["eval"]["every"])
    numbers = compare.training_numbers(prog, ref, rcfg.strategy)
    if leaves:
        return numbers, compare.leaf_gaps(prog, ref, rcfg.strategy)
    return numbers


def round_flops(cell) -> float:
    cfg, tr = cell.config, cell.traffic["training"]
    m = cfg["data"]["num_items"]
    m_s = counts.num_select(m, tr["strategy"], tr["keep_fraction"])
    return counts.round_ops(m, m_s, cfg["theta"], cfg["num_factors"],
                            tr["strategy"])


def run(args, cell, jax, devices, counter, process_start, log) -> dict:
    """One training cell: set-up, window, optional trace, comparison."""
    from types import SimpleNamespace

    from bench.harness import spec, trace as trace_mod
    from bench.harness.device import ROOT

    train_j, test_j = device_data(jax, cell.config)
    log(f"data on the chip at {time.perf_counter() - process_start:.3f} s")
    run_ = drive(jax, cell, args.seed, args.seconds, bool(args.trace),
                 counter, train_j, test_j)
    setup_s = run_.setup_end - process_start
    w = window_numbers(run_)
    memory = device.memory_peak_bytes(devices)
    log(f"set-up {setup_s:.3f} s to the first boundary; programs built by "
        f"then: {run_.boundaries[0].compiles}; in all {counter.describe()}")
    log(f"window: {w['rounds']} rounds in {w['window_s']:.6f} s, "
        f"{w['compiles_in_window']} compilations inside the window; "
        f"{run_.gc}")
    cfg, tr = cell.config, cell.traffic["training"]
    m = cfg["data"]["num_items"]
    m_s = counts.num_select(m, tr["strategy"], tr["keep_fraction"])
    k = cfg["num_factors"]
    log(f"bytes per round: down {m_s * (k + 4)} (int8 rows + scales), up "
        f"{cfg['theta'] * m_s * (k + 4)} ({cfg['theta']} clients)")

    if run_.last_state is not None:
        log(f"P@10 at the window's end (round "
            f"{run_.boundaries[run_.window[1]].round}, 512 users): "
            f"{precision_at_10(run_.engine, run_.last_state, train_j, test_j)!r}")
    t0 = time.perf_counter()
    numbers = check(jax, cell, args.seed, run_.first_state, train_j)
    log(f"reference comparison took {time.perf_counter() - t0:.3f} s")
    verdict = compare.judge(numbers, compare.load_limits(ROOT, cell.name))
    failed = run_.publish_failures + run_.hook_failures
    dev = dict(device.describe(devices), memory_peak_bytes=memory)
    out = {"correct": verdict["ok"] and failed == 0 and
           w["compiles_in_window"] == 0,
           "attempted": w["rounds"], "failed": failed,
           "checks": verdict["checks"], "device": dev}
    if not args.trace:
        values = {"setup_s": setup_s, "rounds_per_s": w["rounds_per_s"]}
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end if m["name"] in values}
        return out
    if run_.traced is None:
        raise RuntimeError("the traced chunks did not complete")
    summary = trace_mod.load(trace_mod.find_xplane(run_.trace_dir),
                             chips=len(devices))
    ctx = SimpleNamespace(
        cell=cell, device_kind=devices[0].device_kind, summary=summary,
        rounds_per_s=w["rounds_per_s"], publish_s=w["publish_s"],
        round_flops=round_flops(cell), num_select=m_s,
        traced_rounds=(run_.boundaries[run_.traced[1]].round
                       - run_.boundaries[run_.traced[0]].round))
    out["metrics"] = spec.read_per_layer(cell, ctx)
    dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
    out["breakdown"] = {"device_ops": summary.top_ops(),
                        "idle_gaps": summary.top_gaps()}
    return out

