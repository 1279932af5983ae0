"""Prove that FCF-BTS training and compressed serving run on a TPU.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # only the sharded-rounds phase (2x2)

One process drives the system's normal entry points at the paper's lastfm
width (Table 2: 1,892 users x 17,632 items, K=25, Theta=100, keep 0.1):

  train    ``load_dataset`` + ``run_fcf_simulation`` (scan engine, BTS, int8
           wire, 20 rounds, eval every 10) publishing into a
           ``ServingEngine`` through ``snapshot_hook``;
  serve    ``recommend`` calls in each default bucket (8, 64, 256);
  kernels  every main-path Pallas kernel on the chip against its
           ``kernels/ref.py`` oracle, plus int8 scoring at the 131,072-item
           serving catalog;
  engines  a 5-round ``backend="scan"`` run against ``backend="python"``.

``--four-chips`` instead runs ``backend="shard"`` over a 4-device mesh
against ``backend="scan"`` with the same 4-block cohort split.

It exits non-zero, and prints no result line, when JAX finds no TPU, when
``REPRO_FORCE_REF`` routes kernels to their oracles, or when any check
fails. The last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

DATASET = "lastfm"
ROUNDS, EVAL_EVERY, THETA, K, KEEP = 20, 10, 100, 25, 0.1
SERVE_M = 131_072            # the serving catalog BENCH_serving.json headlines
TOP_N = 10


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def refuse(reason: str) -> None:
    print(f"chip_smoke: refused: {reason}", file=sys.stderr, flush=True)
    sys.exit(2)


class Checks:
    """Phase timing (XLA compile time apart) and the list of failures."""

    def __init__(self, jax):
        self.failures: list = []
        self._compile_s = 0.0

        def on_event(event, duration, **_):
            # wraps XLA compilation, or the persistent-cache load instead
            if event == "/jax/core/compile/backend_compile_duration":
                self._compile_s += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def check(self, ok: bool, what: str) -> bool:
        log(f"{'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failures.append(what)
        return ok

    def phase(self, name: str, fn, *args):
        c0, t0 = self._compile_s, time.perf_counter()
        try:
            return fn(*args)
        except Exception:       # noqa: BLE001 — report the phase, run the rest
            traceback.print_exc()
            self.failures.append(f"phase {name} raised")
            return None
        finally:
            wall = time.perf_counter() - t0
            comp = self._compile_s - c0
            log(f"phase {name}: wall {wall:.3f} s = xla compile {comp:.3f} s"
                f" + rest {wall - comp:.3f} s")


def _max_abs(a, b) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


# --------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------- #
def phase_train(chk: Checks, data):
    import jax.numpy as jnp
    import numpy as np

    from repro.compress import CodecConfig
    from repro.core.payload import make_selector
    from repro.federated.simulation import FLSimConfig, run_fcf_simulation
    from repro.serve import ServingEngine, ServingModel

    spec, train, test = data
    engine = ServingEngine(ServingModel.from_dense(
        CodecConfig(name="int8"), jnp.zeros((spec.num_items, K), jnp.float32)))
    cfg = FLSimConfig(
        backend="scan", strategy="bts", codec="int8", keep_fraction=KEEP,
        theta=THETA, num_factors=K, rounds=ROUNDS, eval_every=EVAL_EVERY,
        eval_user_chunk=256, snapshot_hook=engine.publisher(), seed=0)
    res = run_fcf_simulation(train, test, cfg)

    for row in res.history.rows:
        log(f"eval round {row['step']}: P@10 {row['precision']!r} "
            f"R@10 {row['recall']!r} MAP@10 {row['map']!r}")
    p10 = res.history.series("precision")
    chk.check(len(p10) == ROUNDS // EVAL_EVERY
              and all(np.isfinite(v) for v in p10),
              f"finite P@10 at each of {ROUNDS // EVAL_EVERY} evals")
    chk.check(bool(np.isfinite(np.asarray(res.server_state.q)).all()),
              "trained Q is finite")

    # bytes against core.payload's pricing of the same selector + codec
    sel = make_selector("bts", spec.num_items, K, keep_fraction=KEEP,
                        codec="int8")
    per_round = sel.round_payload_bytes
    want_down, want_up = ROUNDS * per_round, ROUNDS * THETA * per_round
    log(f"bytes down {res.bytes_down} (core.payload: {want_down}), bytes up "
        f"{res.bytes_up} (core.payload: {want_up}), {sel.num_select} of "
        f"{spec.num_items} rows per round")
    chk.check(res.bytes_down == want_down and res.bytes_up == want_up,
              "bytes equal core.payload pricing")
    chk.check(int(float(res.server_state.bytes_down)) == want_down,
              "in-state downlink counter equals the pricing")

    stats = engine.stats()
    log(f"hook failures {res.hook_failures}, publish failures "
        f"{stats.publish_failures}, installs {stats.installs}, "
        f"model version {stats.version}")
    chk.check(res.hook_failures == 0, "no snapshot_hook failures")
    chk.check(stats.publish_failures == 0
              and stats.installs == ROUNDS // EVAL_EVERY
              and stats.version >= ROUNDS // EVAL_EVERY,
              "every eval published a new serving model")
    return res, engine


def phase_round_step_kernels(chk: Checks, data):
    """Count the Pallas kernels in the compiled round step the train phase
    scans (the same builder, with the data passed as an argument)."""
    import jax
    import jax.numpy as jnp

    from repro.federated.simulation import (
        FLSimConfig, _build, _make_round_fn,
    )

    spec, train, test = data
    train_j, test_j = jnp.asarray(train), jnp.asarray(test)
    cfg = FLSimConfig(strategy="bts", codec="int8", keep_fraction=KEEP,
                      theta=THETA, num_factors=K, rounds=EVAL_EVERY, seed=0)
    setup = _build(train_j, test_j, cfg)

    def chunk(state, cohorts, x):
        round_fn = _make_round_fn(x, setup)
        return jax.lax.scan(lambda s, c: (round_fn(s, c)[0], None),
                            state, cohorts)

    text = jax.jit(chunk).lower(setup.state0, jnp.asarray(setup.cohorts),
                                train_j).compile().as_text()
    n = text.count('custom_call_target="tpu_custom_call"')
    log(f"tpu_custom_calls in the compiled round step: {n}")
    chk.check(n > 0, "the round step runs Pallas kernels")


def phase_serve(chk: Checks, data, trained):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.cf.local import solve_user_factors
    from repro.kernels import ref

    spec, train, test = data
    res, engine = trained
    model = engine.model
    users = np.random.default_rng(1).choice(spec.num_users, 256,
                                            replace=False)
    p_all = solve_user_factors(res.server_state.q, jnp.asarray(train[users]))
    mask_all = jnp.asarray(train[users])
    for b in (8, 40, 256):          # buckets 8, 64 (padded), 256
        p, mask = p_all[:b], mask_all[:b]
        t0 = time.perf_counter()
        vals, ids = jax.block_until_ready(
            engine.recommend(p, train_mask=mask))
        dt = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want_v, want_i = ref.wire_topn_ref(
                model.cfg, model.wire, p, model.dim, TOP_N, train_mask=mask,
                block_m=engine.block_m)
        vals, ids = np.asarray(vals), np.asarray(ids)
        hit_mask = np.take_along_axis(np.asarray(mask), ids, axis=1)
        log(f"recommend B={b} (bucket {engine._bucket_for(b)}): "
            f"{dt * 1e3:.3f} ms, first ids {ids[0, :5].tolist()}")
        chk.check(ids.shape == (b, TOP_N) and np.isfinite(vals).all()
                  and ((0 <= ids) & (ids < spec.num_items)).all()
                  and not hit_mask.any(),
                  f"B={b}: finite top-{TOP_N} of unseen items")
        _topn_agrees(chk, f"recommend B={b} vs wire_topn_ref", vals, ids,
                     want_v, want_i)
    stats = engine.stats()
    chk.check(stats.requests == 3 and stats.users == 8 + 40 + 256,
              "engine counted every request")


def _topn_agrees(chk, what, got_v, got_i, want_v, want_i):
    """Scores agree to float32 rounding; ids agree except where two scores
    are within that rounding of each other (a legitimate near-tie swap)."""
    import numpy as np

    got_v, got_i = np.asarray(got_v), np.asarray(got_i)
    want_v, want_i = np.asarray(want_v), np.asarray(want_i)
    tol = 1e-5 * max(1.0, float(np.abs(want_v).max()))
    dv = _max_abs(got_v, want_v)
    swapped = got_i != want_i
    near_tie = np.abs(got_v - want_v) <= tol
    log(f"{what}: max |d score| {dv!r}, ids differing {int(swapped.sum())} "
        f"of {swapped.size}")
    chk.check(dv <= tol and not (swapped & ~near_tie).any(), what)


def phase_kernels(chk: Checks):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compress import CodecConfig, encode
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    m, m_s = 17_632, int(round(KEEP * 17_632))
    shard = m // 4

    def arr(*shape, dtype=jnp.float32):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    table, rows = arr(m, K), arr(m_s, K)
    idx = jnp.asarray(rng.choice(m, m_s, replace=False), jnp.int32)
    local = idx - shard          # shard 1 of 4: some rows in range, most not
    codes, scales = ref.gather_quantize_rows_ref(table, jnp.arange(m))
    noise = jnp.asarray(rng.random((m_s, K)), jnp.float32)

    def exact(name, got, want):
        got, want = jax.tree.leaves(got), jax.tree.leaves(want)
        same = all(np.array_equal(np.asarray(g), np.asarray(w))
                   for g, w in zip(got, want))
        diff = max(_max_abs(g, w) for g, w in zip(got, want))
        chk.check(same, f"{name} bit-equal to its oracle (max |d| {diff!r})")

    # the aliased scatters donate their table: each call gets a fresh copy
    def fresh(a):
        return jnp.array(a, copy=True)

    exact("gather_rows", ops.gather_rows(table, idx),
          ref.gather_rows_ref(table, idx))
    exact("scatter_set_rows", ops.scatter_set_rows(fresh(table), idx, rows),
          ref.scatter_set_rows_ref(table, idx, rows))
    exact("scatter_add_rows", ops.scatter_add_rows(fresh(table), idx, rows),
          ref.scatter_add_rows_ref(table, idx, rows))
    exact("gather_quantize_rows", ops.gather_quantize_rows(table, idx),
          ref.gather_quantize_rows_ref(table, idx))
    c8, s8 = ref.gather_quantize_rows_ref(rows, jnp.arange(m_s))
    exact("dequant_scatter_set_rows",
          ops.dequant_scatter_set_rows(fresh(table), idx, c8, s8),
          ref.dequant_scatter_set_rows_ref(table, idx, c8, s8))
    blk = table[shard:2 * shard]
    exact("gather_rows_block", ops.gather_rows_block(blk, local),
          ref.gather_rows_block_ref(blk, local))
    exact("scatter_set_rows_block",
          ops.scatter_set_rows_block(fresh(blk), local, rows),
          ref.scatter_set_rows_block_ref(blk, local, rows))
    exact("gather_quantize_rows_block",
          ops.gather_quantize_rows_block(blk, local),
          ref.gather_quantize_rows_block_ref(blk, local))
    exact("gather_dequant_rows", ops.gather_dequant_rows(codes, scales, idx),
          ref.gather_dequant_rows_ref(codes, scales, idx))
    for name, nz in (("quant_scatter_set_rows", None),
                     ("quant_scatter_set_rows stochastic", noise)):
        exact(name, ops.quant_scatter_set_rows(
            fresh(codes), fresh(scales), idx, rows, nz),
            ref.quant_scatter_set_rows_ref(codes, scales, idx, rows, nz))
    cb, sb = codes[shard:2 * shard], scales[shard:2 * shard]
    exact("gather_dequant_rows_block",
          ops.gather_dequant_rows_block(cb, sb, local),
          ref.gather_dequant_rows_block_ref(cb, sb, local))
    exact("quant_scatter_set_rows_block",
          ops.quant_scatter_set_rows_block(fresh(cb), fresh(sb), local, rows,
                                           noise),
          ref.quant_scatter_set_rows_block_ref(cb, sb, local, rows, noise))

    # fused FCF gradient: float32 matmuls, so float32-rounding agreement
    q_star, p = arr(m_s, K), arr(THETA, K)
    x = jnp.asarray(rng.random((THETA, m_s)) < 0.05, jnp.float32)
    got = ops.fcf_item_gradients(q_star, p, x, alpha=4.0, l2=0.0)
    with jax.default_matmul_precision("highest"):
        want = ref.fcf_grad_ref(q_star, p, x, l2=0.0, alpha=4.0)
    want = np.asarray(want)
    tol = 1e-5 * float(np.abs(want).max())
    d = _max_abs(got, want)
    chk.check(d <= tol, f"fcf_item_gradients within float32 rounding of its "
              f"oracle (max |d| {d!r}, tol {tol!r})")

    # scoring: every codec with a kernel, at the eval block (train-masked)
    # and at the serving block, then int8 at the serving catalog
    def topn(codec, n_items, b, block, masked):
        cfg = CodecConfig(name=codec)
        wire = encode(cfg, arr(n_items, K))
        pb = arr(b, K)
        mask = (jnp.asarray(rng.random((b, n_items)) < 0.05, jnp.float32)
                if masked else None)
        t0 = time.perf_counter()
        got_v, got_i = jax.block_until_ready(ops.wire_topn(
            cfg, wire, pb, K, TOP_N, train_mask=mask, block_m=block))
        dt = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want_v, want_i = ref.wire_topn_ref(cfg, wire, pb, K, TOP_N,
                                               train_mask=mask, block_m=block)
        _topn_agrees(chk, f"wire_topn {codec} M={n_items} B={b} block={block}"
                     f"{' masked' if masked else ''} ({dt * 1e3:.3f} ms, "
                     f"compile included)", got_v, got_i, want_v, want_i)

    for codec in ("fp32", "int8", "int4"):
        topn(codec, m, 256, ops.fit_block_m(256, K, TOP_N), masked=True)
        topn(codec, m, 8, 1024, masked=False)
    topn("int8", SERVE_M, 256, 1024, masked=False)


def phase_engines(chk: Checks, data):
    import numpy as np

    from repro.federated.simulation import FLSimConfig, run_fcf_simulation

    spec, train, test = data
    base = dict(strategy="bts", codec="int8", keep_fraction=KEEP,
                theta=THETA, num_factors=K, rounds=5, eval_every=5,
                record_selections=True, seed=0)
    scan = run_fcf_simulation(train, test, FLSimConfig(backend="scan", **base))
    py = run_fcf_simulation(train, test, FLSimConfig(backend="python", **base))
    q_s, q_p = np.asarray(scan.server_state.q), np.asarray(py.server_state.q)
    dq = _max_abs(q_s, q_p)
    log(f"scan vs python, 5 rounds: selections identical "
        f"{bool(np.array_equal(scan.selections, py.selections))}, "
        f"max |dQ| {dq!r}, Q bitwise equal {bool(np.array_equal(q_s, q_p))}")
    chk.check(np.array_equal(scan.selections, py.selections),
              "scan and python engines select identical payloads")
    chk.check(dq <= 1e-4 * float(np.abs(q_p).max()),
              "scan and python engines reach the same Q")


def phase_four_chips(chk: Checks, data):
    import jax
    import numpy as np

    from repro.federated.simulation import FLSimConfig, run_fcf_simulation

    spec, train, test = data
    base = dict(strategy="bts", codec="int8", keep_fraction=KEEP,
                theta=THETA, num_factors=K, rounds=10, eval_every=10,
                record_selections=True, seed=0)
    shard = run_fcf_simulation(
        train, test, FLSimConfig(backend="shard", mesh_shards=4, **base))
    scan = run_fcf_simulation(
        train, test, FLSimConfig(backend="scan", cohort_shards=4, **base))

    tables = [leaf for leaf in jax.tree.leaves(shard.server_state)
              if leaf.ndim == 2 and leaf.shape[0] == spec.num_items]
    spread = [len({s.device for s in t.addressable_shards}) == 4
              and all(s.data.shape == (spec.num_items // 4, K)
                      for s in t.addressable_shards) for t in tables]
    log(f"{len(tables)} (M, K) state tables, each row-sharded over 4 devices:"
        f" {all(spread)}; devices "
        f"{sorted(str(d) for d in shard.server_state.q.sharding.device_set)}")
    chk.check(len(tables) >= 3 and all(spread),
              "the (M, K) tables live on 4 distinct devices")
    q_sh, q_sc = (np.asarray(shard.server_state.q),
                  np.asarray(scan.server_state.q))
    dq = _max_abs(q_sh, q_sc)
    log(f"shard vs scan, 10 rounds: selections identical "
        f"{bool(np.array_equal(shard.selections, scan.selections))}, "
        f"max |dQ| {dq!r}, Q bitwise equal {bool(np.array_equal(q_sh, q_sc))}")
    chk.check(np.array_equal(shard.selections, scan.selections),
              "shard and scan engines select identical payloads")
    chk.check(bool(np.allclose(q_sh, q_sc, rtol=1e-5,
                               atol=1e-6 * float(np.abs(q_sc).max()))),
              "shard and scan engines reach allclose Q")


# --------------------------------------------------------------------- #
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only backend='shard' on 4 chips against scan")
    args = ap.parse_args()
    chips = 4 if args.four_chips else 1

    if os.environ.get("REPRO_FORCE_REF", "0") != "0":
        refuse("REPRO_FORCE_REF is set, which routes every kernel to its "
               "jnp oracle; unset it to run the Pallas kernels")
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        refuse(f"JAX found no TPU (platform {platform!r}); this smoke runs "
               f"only on the chip")
    if len(devices) < chips:
        refuse(f"{chips} chips needed, JAX sees {len(devices)}")
    kind = devices[0].device_kind
    log(f"device {platform} {kind!r} x{len(devices)}, jax {jax.__version__}")

    from repro.data.synthetic import load_dataset
    from repro.utils.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    chk = Checks(jax)
    data = chk.phase("data", load_dataset, DATASET, 0)
    if data is None:
        sys.exit(1)
    spec = data[0]
    log(f"dataset {spec.name}: {spec.num_users} users x {spec.num_items} "
        f"items, {int(data[1].sum())} train interactions")

    if args.four_chips:
        chk.phase("four-chip shard vs scan", phase_four_chips, chk, data)
    else:
        trained = chk.phase("train + publish", phase_train, chk, data)
        chk.phase("round-step kernel count", phase_round_step_kernels, chk,
                  data)
        if trained is not None:
            chk.phase("serve", phase_serve, chk, data, trained)
        chk.phase("kernel parity", phase_kernels, chk)
        chk.phase("scan vs python", phase_engines, chk, data)

    if chk.failures:
        for f in chk.failures:
            print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
        sys.exit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
