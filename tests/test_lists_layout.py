"""Per-user item lists through the program's normal training path.

``run_fcf_simulation`` takes each split as a CSR triple ``(indptr, indices,
(users, items))`` of host arrays, lays the train lists out on the device as
:class:`UserLists` and builds every round's (Theta, M_s) block from them
(``_cohort_block`` -> ``_cohort_block_lists``). The plain reference is the
same data as a dense matrix through the dense path: the two must agree bit
for bit. The benchmark's own lists reference (``bench/harness/reference``)
must read the same numbers against either.
"""
import json
import re
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.federated import (FLSimConfig, run_fcf_simulation, run_seed_sweep,
                             run_strategy_sweep)
from repro.federated import simulation as sim
from repro.faults import FaultConfig
from repro.obs import InMemorySink, ObsConfig

USERS, ITEMS = 40, 120
EMPTY_USER = 3
HEAVY_USERS = (5, 17, 29)


def _triple(users=USERS, items=ITEMS, seed=0):
    """Sorted per-user item ids: degrees 1-24, one user without any, a few
    heavy users with most of the catalog."""
    rng = np.random.default_rng(seed)
    deg = rng.integers(1, 25, users)
    deg[EMPTY_USER] = 0
    deg[list(HEAVY_USERS)] = [items - 7, items // 2, items - 1]
    indptr = np.zeros(users + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = np.concatenate(
        [np.sort(rng.choice(items, d, replace=False)) for d in deg]
    ).astype(np.int32)
    return indptr, indices, (users, items)


def _dense(triple):
    indptr, indices, (n, m) = triple
    out = np.zeros((n, m), np.float32)
    out[np.repeat(np.arange(n), np.diff(indptr)), indices] = 1.0
    return out


def _splits(seed=0, users=USERS, items=ITEMS):
    """Disjoint train and test lists of one interaction set."""
    indptr, indices, shape = _triple(users, items, seed)
    owner = np.repeat(np.arange(users), np.diff(indptr))
    to_test = np.random.default_rng(seed + 1).random(indices.shape[0]) < 0.2
    out = []
    for keep in (~to_test, to_test):
        ptr = np.zeros(users + 1, np.int64)
        np.cumsum(np.bincount(owner[keep], minlength=users), out=ptr[1:])
        out.append((ptr, indices[keep], shape))
    return tuple(out)


def _device_lists(triple, piece, chunk=None):
    indptr, indices, (_, m) = triple
    chunk = chunk or sim._list_chunk(np.diff(indptr))
    return sim.UserLists(indptr=jnp.asarray(indptr.astype(np.int32)),
                         indices=jnp.asarray(sim._id_rows(indices, chunk)),
                         num_items=m, piece=piece)


def _assert_blocks_equal(triple, ids, idx, piece, chunk=None):
    ids, idx = jnp.asarray(ids, jnp.int32), jnp.asarray(idx, jnp.int32)
    got = jax.jit(sim._cohort_block)(_device_lists(triple, piece, chunk),
                                     ids, idx)
    want = jax.jit(sim._cohort_block)(jnp.asarray(_dense(triple)), ids, idx)
    assert got.dtype == want.dtype == jnp.float32
    assert got.shape == (ids.shape[0], idx.shape[0])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    return got


def _triple_of(deg, items=ITEMS, seed=0):
    """Sorted per-user item ids of the given degrees."""
    rng = np.random.default_rng(seed)
    indptr = np.zeros(len(deg) + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = np.concatenate(
        [np.sort(rng.choice(items, d, replace=False)) for d in deg]
        + [np.zeros(0, np.int64)]).astype(np.int32)
    return indptr, indices, (len(deg), items)


# --------------------------------------------------------------------- #
# the cohort block
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("piece", [8, 128])
@pytest.mark.parametrize("idx_kind", ["sorted", "unsorted", "all_columns"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_block_from_lists_equals_dense_on_random_cohorts(seed, idx_kind,
                                                         piece):
    rng = np.random.default_rng(seed)
    triple = _triple(seed=seed)
    ids = rng.choice(USERS, 10, replace=False)
    idx = {"sorted": np.sort(rng.choice(ITEMS, 17, replace=False)),
           "unsorted": rng.choice(ITEMS, 17, replace=False),
           "all_columns": np.arange(ITEMS)}[idx_kind]
    got = _assert_blocks_equal(triple, ids, idx, piece)
    if idx_kind == "all_columns":
        assert float(got.sum()) == float(np.diff(triple[0])[ids].sum())


def test_block_of_a_cohort_with_an_empty_list_and_repeated_pad_ids():
    rng = np.random.default_rng(4)
    triple = _triple()
    ids = np.concatenate([[EMPTY_USER], rng.choice(USERS, 6, replace=False),
                          [0, 0, EMPTY_USER]])
    got = _assert_blocks_equal(triple, ids, np.arange(ITEMS), 8)
    assert float(got[0].sum()) == 0.0


def test_block_of_the_heaviest_users():
    triple = _triple()
    heavy = np.argsort(np.diff(triple[0]))[-8:]
    assert set(HEAVY_USERS) <= set(heavy.tolist())
    idx = np.random.default_rng(5).choice(ITEMS, 40, replace=False)
    _assert_blocks_equal(triple, heavy, idx, 16)


def _rows(indptr, chunk):
    """Rows of ``chunk`` ids that each user's list touches, counted."""
    flat = np.arange(indptr[-1]) // chunk
    return np.array([len(set(flat[a:b])) for a, b in zip(indptr, indptr[1:])])


@pytest.mark.parametrize("extra", [0, 1], ids=["exactly", "one_more"])
def test_block_when_the_ids_fill_the_capacity_or_one_more_than_a_piece(
        extra):
    """The Theta users whose lists touch the most rows hold the capacity
    (the most rows a round can walk): a piece of exactly that many rows
    walks it in one step, and a piece one row smaller leaves one row for a
    second step."""
    triple = _triple()
    theta, chunk = 6, 8
    rows = _rows(triple[0], chunk)
    heavy = np.argsort(rows)[-theta:]
    cap_rows = int(np.sort(rows)[-theta:].sum())
    assert int(rows[heavy].sum()) == cap_rows
    _assert_blocks_equal(triple, heavy, np.arange(ITEMS),
                         (cap_rows - extra) * chunk, chunk)


C = 8
# name -> (degrees, cohort, rows per step, None for all the cohort's rows
# less ``short``): users of degree C-1, C, C+1 and 2C at offsets that do and
# do not start a row, the last user of ``indices`` (its final row is padded
# past the ids' end), a cohort whose rows fill exactly one step or need one
# more, and lists shorter than one row overall
CHUNK_EDGES = {
    "degrees_around_the_chunk": (
        [C - 1, C, C + 1, 2 * C, 0, 1, 3 * C - 1, 5, C, 2 * C, C + 1],
        list(range(11)), 2, 0),
    "last_user_in_a_padded_row": (
        [3 * C, C + 3, 2, 2 * C, C + 1], [4, 1, 3], 4, 0),
    "last_user_shorter_than_a_chunk": (
        [3 * C, C + 3, 2 * C, 2], [3, 0], 2, 0),
    "rows_fill_one_step": (
        [C, C + 1, 2 * C, C - 1, 3, 4 * C], [0, 1, 2, 5], None, 0),
    "rows_need_one_more_step": (
        [C, C + 1, 2 * C, C - 1, 3, 4 * C], [0, 1, 2, 5], None, 1),
    "all_lists_shorter_than_a_chunk": (
        [1, 2, 0, 3], [3, 1, 2, 0], 2, 0),
}


@pytest.mark.parametrize("chunk", [C, 2 * C])
@pytest.mark.parametrize("case", sorted(CHUNK_EDGES))
def test_block_from_lists_equals_dense_at_the_chunk_edges(case, chunk):
    deg, ids, per_step, short = CHUNK_EDGES[case]
    triple = _triple_of(np.asarray(deg), items=64, seed=len(case))
    if per_step is None:
        per_step = int(_rows(triple[0], chunk)[ids].sum()) - short
    # item 0, the rows' padding, is always among the columns
    for idx in (np.random.default_rng(9).choice(np.arange(1, 64), 40,
                                                 replace=False),
                np.arange(64)):
        _assert_blocks_equal(triple, ids, idx, per_step * chunk, chunk)


def test_list_piece_is_set_by_the_data_and_theta_alone():
    # MovieLens-25M's train lists: 20,050,397 ids over 162,541 users
    assert sim._list_piece(1000, 162_541, 20_050_397) == 16_384
    assert sim._list_piece(10, 60, 1_400) == 128


def _ml25m_train_degrees():
    """MovieLens-25M's train degrees: the benchmark generator's degree draw
    (the draws before it, then ``user_degrees``) and its per-user cut."""
    from bench.harness import data
    from bench.harness.device import ROOT

    ds = json.loads((ROOT / "bench/configs/fcf-ml25m.json").read_text())
    ds = ds["data"]
    n, m, k0 = ds["num_users"], ds["num_items"], ds["latent_dim"]
    rng = np.random.default_rng(ds["seed"])
    rng.standard_normal((n, k0))
    rng.standard_normal((m, k0))
    rng.permutation(m)
    deg = data.user_degrees(n, m, ds["num_interactions"], ds["min_degree"],
                            rng)
    return np.array([data._cut(int(d), ds["train_frac"]) for d in deg])


def test_list_chunk_and_chunks_per_step_are_set_by_the_data_alone():
    deg = _ml25m_train_degrees()
    assert int(deg.sum()) == 20_050_397 and np.median(deg) == 74
    chunk = sim._list_chunk(deg)
    assert chunk == 16                        # a quarter of 74, down
    assert sim._list_piece(1000, 162_541, 20_050_397) // chunk == 1024
    # the tiny sets here have medians of a dozen or less: the floor
    assert sim._list_chunk(np.diff(_triple()[0])) == 8
    assert sim._list_chunk(np.array([1, 2, 0, 3])) == 8
    assert sim._list_chunk(np.zeros(0, np.int64)) == 8
    assert sim._list_chunk(np.full(10, 64)) == 16
    assert sim._list_chunk(np.full(10, 63)) == 8


def test_id_rows_pad_the_last_row_and_keep_one_row():
    np.testing.assert_array_equal(
        sim._id_rows(np.arange(1, 11, dtype=np.int32), 4),
        [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 0, 0]])
    assert sim._id_rows(np.zeros(0, np.int32), 8).shape == (1, 8)


# --------------------------------------------------------------------- #
# the whole loop: lists against the same data as a dense matrix
# --------------------------------------------------------------------- #
def _cfg(strategy, **kw):
    base = dict(strategy=strategy, keep_fraction=0.25, rounds=10, theta=8,
                eval_every=5, eval_users=16, num_factors=4, codec="int8",
                seed=2147483911, backend="scan", record_selections=True)
    base.update(kw)
    return FLSimConfig(**base)


def _assert_runs_bit_equal(a, b):
    la = jax.tree_util.tree_leaves_with_path(a.server_state)
    lb = jax.tree.leaves(b.server_state)
    assert len(la) == len(lb)
    for (path, u), v in zip(la, lb):
        u, v = np.asarray(u), np.asarray(v)
        assert u.dtype == v.dtype and np.array_equal(u, v), \
            jax.tree_util.keystr(path)
    for key in ("precision", "recall", "f1", "map"):
        assert a.history.series(key) == b.history.series(key), key
    np.testing.assert_array_equal(a.selections, b.selections)
    assert (a.bytes_down, a.bytes_up) == (b.bytes_down, b.bytes_up)


def _both(cfg, seed=0):
    tr, te = _splits(seed)
    dense = run_fcf_simulation(_dense(tr), _dense(te), cfg)
    lists = run_fcf_simulation(tr, te, cfg)
    return dense, lists


@pytest.mark.parametrize("strategy", ["bts", "full"])
def test_lists_run_is_bit_equal_to_dense(strategy):
    dense, lists = _both(_cfg(strategy))
    _assert_runs_bit_equal(dense, lists)
    assert lists.rounds == 10 and len(lists.history.series("f1")) == 2
    assert int(lists.server_state.opt.t.max()) > 0


@pytest.mark.parametrize("variant", ["cohort_shards", "obs", "faults"])
def test_every_scan_variant_takes_lists(variant):
    """The scan engine's blocked, telemetry and faulted chunk programs all
    build the block through the same ``_cohort_block``."""
    kw = {"cohort_shards": {"cohort_shards": 3},
          "obs": {"obs": ObsConfig(enabled=True, sink=InMemorySink())},
          "faults": {"faults": FaultConfig(enabled=True, dropout_rate=0.3,
                                           straggler_rate=0.1)}}[variant]
    dense, lists = _both(_cfg("bts", **kw))
    _assert_runs_bit_equal(dense, lists)


# --------------------------------------------------------------------- #
# against the benchmark's lists reference
# --------------------------------------------------------------------- #
def _bench_cell():
    from bench.harness.device import ROOT

    cfg = json.loads((ROOT / "bench/configs/fcf-ml25m.json").read_text())
    cfg.update(theta=8, num_factors=4,
               eval={"every": 5, "users": 16, "top_n": 10})
    mix = json.loads((ROOT / "bench/traffic/train.bts.json").read_text())
    return cfg, dict(mix, max_rounds=5), ROOT


def test_program_on_lists_reads_as_dense_against_the_reference():
    from bench.harness import compare, reference, train

    cfg, mix, root = _bench_cell()
    seed = 3037000493
    tr, te = _splits(users=60, items=300)
    sim_cfg = train.sim_config(cfg, mix, seed, None)
    rcfg = reference.ref_round_config(cfg, mix, 300)
    limits = compare.load_limits(root, "mind.train.bts")
    read = {}
    for layout, (x, y) in (("lists", (tr, te)),
                           ("dense", (_dense(tr), _dense(te)))):
        res = run_fcf_simulation(x, y, sim_cfg)
        ref = reference.run_training(rcfg, x if layout == "lists"
                                     else jnp.asarray(x), seed, rounds=5)
        prog = train.program_state(res.server_state)
        read[layout] = compare.training_numbers(prog, ref, "bts")
        assert compare.judge(read[layout], limits)["ok"], read[layout]
    assert read["lists"] == read["dense"]


# --------------------------------------------------------------------- #
# what the compiled programs hold
# --------------------------------------------------------------------- #
class _ChunkText:
    """Records the StableHLO text of each scan chunk program the loop
    lowers (``jax.jit`` of a function named ``scan_chunk``)."""

    def __init__(self, monkeypatch):
        self.texts = []
        real = jax.jit

        def jit(fn, *a, **k):
            jitted = real(fn, *a, **k)
            if getattr(fn, "__name__", "") != "scan_chunk":
                return jitted

            def call(*args):
                self.texts.append(jitted.lower(*args).as_text())
                return jitted(*args)
            return call

        monkeypatch.setattr(jax, "jit", jit)


def test_compiled_programs_hold_no_users_by_items_array(tmp_path,
                                                         monkeypatch):
    users, items = 61, 293               # shapes no other array has
    tr, te = _splits(seed=7, users=users, items=items)
    width = sim._list_chunk(np.diff(tr[0]))
    rows = sim._id_rows(tr[1], width).shape[0]
    chunks = _ChunkText(monkeypatch)
    was = jax.config.values["jax_dump_ir_to"]
    jax.config.update("jax_dump_ir_to", str(tmp_path))
    try:
        run_fcf_simulation(tr, te, _cfg("bts", rounds=5, eval_users=20))
    finally:
        jax.config.update("jax_dump_ir_to", was)
    dumped = [p.read_text() for p in Path(tmp_path).glob("*.mlir")]
    assert any("scan_chunk" in t for t in dumped)
    assert any(f"tensor<20x{items}xf32>" in t for t in dumped)  # eval rows
    for text in dumped + chunks.texts:
        assert f"{users}x{items}x" not in text
    (chunk,) = chunks.texts
    main = re.search(r"func\.func public @main\((.*?)\)\s*->", chunk,
                     re.S).group(1)
    assert f"tensor<{rows}x{width}xi32>" in main          # the ids
    assert f"tensor<{users + 1}xi32>" in main             # the offsets
    assert not re.search(rf"constant dense<.*tensor<{rows}x{width}xi32>",
                         chunk)


def test_a_new_seed_lowers_the_same_chunk_program(monkeypatch):
    """No static shape depends on the seed: a second seed's chunk program is
    the first's, so the persistent compile cache serves it."""
    tr, te = _splits()
    chunks = _ChunkText(monkeypatch)
    for seed in (1, 2654435761):
        run_fcf_simulation(tr, te, _cfg("bts", rounds=5, seed=seed,
                                        record_selections=False))
    first, second = chunks.texts
    assert first == second


# --------------------------------------------------------------------- #
# what is refused, by name
# --------------------------------------------------------------------- #
def _bad(kind):
    indptr, indices, shape = _triple()
    if kind == "not_monotone":
        indptr = indptr.copy()
        indptr[5], indptr[6] = indptr[6], indptr[5]
    elif kind == "first_not_zero":
        indptr = indptr + 1
    elif kind == "last_not_len":
        indices = indices[:-1]
    elif kind == "id_too_large":
        indices = indices.copy()
        indices[3] = ITEMS
    elif kind == "id_negative":
        indices = indices.copy()
        indices[0] = -1
    elif kind == "indptr_length":
        indptr = indptr[:-1]
    elif kind == "too_many_ids":
        indices = np.broadcast_to(np.int32(0), (2 ** 31,))
    return indptr, indices, shape


@pytest.mark.parametrize("kind,match", [
    ("not_monotone", "monotone"),
    ("first_not_zero", "run from 0"),
    ("last_not_len", "run from 0"),
    ("id_too_large", r"\[0, 120\)"),
    ("id_negative", r"\[0, 120\)"),
    ("indptr_length", "users \\+ 1"),
    ("too_many_ids", "2\\*\\*31"),
])
def test_a_bad_triple_is_refused_by_what_is_wrong(kind, match):
    bad = _bad(kind)
    with pytest.raises(ValueError, match=match):
        run_fcf_simulation(bad, _triple(), _cfg("bts"))


def test_splits_must_share_their_layout_and_shape():
    tr, te = _splits()
    with pytest.raises(ValueError, match="CSR triple"):
        run_fcf_simulation(tr, _dense(te), _cfg("bts"))
    other = _triple(users=USERS + 1)
    with pytest.raises(ValueError, match="one \\(users, items\\) shape"):
        run_fcf_simulation(tr, other, _cfg("bts"))


@pytest.mark.parametrize("backend", ["python", "async", "shard"])
def test_other_engines_refuse_lists_by_name(backend):
    tr, te = _splits()
    with pytest.raises(ValueError, match="item lists.*backend='scan' only"):
        run_fcf_simulation(tr, te, _cfg("bts", backend=backend))


def test_the_sweeps_refuse_lists_by_name():
    tr, te = _splits()
    with pytest.raises(ValueError, match="item lists"):
        run_seed_sweep(tr, te, _cfg("bts"), seeds=[0, 1])
    with pytest.raises(ValueError, match="item lists"):
        run_strategy_sweep(tr, te, replace(_cfg("bts"), rounds=5),
                           strategies=("bts",))
