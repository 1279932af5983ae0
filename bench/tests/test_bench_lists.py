"""The per-user item-list layout: one generator for both layouts, the plain
reference from lists, and a lists configuration handed to the program.

The dense generator that the lists replaced is kept here as the reference
they must equal byte for byte.
"""
import json
import shutil
import time
import tracemalloc
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import compare, data, device, reference, serve, spec, train
from bench.harness.device import ROOT

E2E_TRAIN = [{"name": "setup_s", "unit": "s"},
             {"name": "rounds_per_s", "unit": "rounds/s"}]
SEED = 2147483911


def _dense_interactions(ds, seed):
    """The generator as it wrote the dense matrix before the lists."""
    n, m, k0 = ds["num_users"], ds["num_items"], ds["latent_dim"]
    rng = np.random.default_rng(seed)
    u = rng.standard_normal((n, k0)).astype(np.float32)
    v = rng.standard_normal((m, k0)).astype(np.float32)
    ranks = rng.permutation(m) + 1
    pop = (-ds["zipf_exponent"] * np.log(ranks)).astype(np.float32)
    deg = data.user_degrees(n, m, ds["num_interactions"], ds["min_degree"],
                            rng)
    x = np.zeros((n, m), dtype=np.uint8)
    chunk = max(1, int(2e8) // m)
    for start in range(0, n, chunk):
        stop = min(start + chunk, n)
        scores = (ds["signal"] / np.sqrt(k0)) * (u[start:stop] @ v.T) \
            + pop[None, :]
        noisy = scores + rng.gumbel(size=scores.shape).astype(np.float32)
        order = np.argsort(-noisy, axis=1)
        for r, i in enumerate(range(start, stop)):
            x[i, order[r, :deg[i]]] = 1
    return x


def _dense_split(x, train_frac, seed):
    rng = np.random.default_rng(seed)
    tr = np.zeros_like(x)
    te = np.zeros_like(x)
    for i in range(x.shape[0]):
        items = np.flatnonzero(x[i])
        rng.shuffle(items)
        cut = max(1, int(round(train_frac * len(items))))
        cut = min(cut, len(items) - 1) if len(items) > 1 else cut
        tr[i, items[:cut]] = 1
        te[i, items[cut:]] = 1
    return tr, te


def _block(**kw):
    ds = {"name": "tiny", "num_users": 60, "num_items": 300,
          "num_interactions": 1800, "latent_dim": 16, "signal": 4.0,
          "zipf_exponent": 1.0, "min_degree": 5, "train_frac": 0.8,
          "seed": 0}
    ds.update(kw)
    return ds


BLOCKS = {
    "tiny": _block(),
    "few_users_many_items": _block(num_users=23, num_items=1999,
                                   num_interactions=3000, seed=4),
    "many_users_few_items": _block(num_users=700, num_items=40,
                                   num_interactions=9000, min_degree=1,
                                   latent_dim=3, seed=9),
    # a popularity so steep that all but the first ~30 ranks are -inf: the
    # users of degree above that tie at the boundary of their top set
    "ties_at_the_boundary": _block(zipf_exponent=1e38, seed=2),
}


@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_lists_written_dense_are_the_dense_generator_byte_for_byte(name):
    ds = BLOCKS[name]
    with np.errstate(over="ignore", invalid="ignore"):
        want = _dense_interactions(ds, ds["seed"])
        x = data.interactions(ds, ds["seed"], block_values=7 * ds["num_items"],
                              workers=3)
    assert x.indices.dtype == np.int32 and x.indptr.dtype == np.int64
    assert np.array_equal(data.densify(x), want)
    for a, b in zip(x.indptr[:-1], x.indptr[1:]):
        assert np.all(np.diff(x.indices[a:b]) > 0)
    tr, te = data.split(x, ds["train_frac"], ds["seed"] + 1)
    want_tr, want_te = _dense_split(want, ds["train_frac"], ds["seed"] + 1)
    assert np.array_equal(data.densify(tr), want_tr)
    assert np.array_equal(data.densify(te), want_te)


def test_the_tie_block_has_ties_at_the_boundary():
    ds = BLOCKS["ties_at_the_boundary"]
    with np.errstate(over="ignore"):
        pop = (-ds["zipf_exponent"] * np.log(np.arange(1, 301))
               ).astype(np.float32)
    finite = int(np.isfinite(pop).sum())
    with np.errstate(over="ignore"):
        x = data.interactions(ds, ds["seed"])
    assert np.diff(x.indptr).max() > finite


@pytest.mark.parametrize("row, d, want", [
    ([5.0, 1.0, 3.0, 1.0, 2.0], 2, [1, 3]),            # no tie at the cut
    ([0.0, 0.0, 0.0, 0.0, 1.0], 2, None),              # tie across it
    ([np.inf, 0.0, np.inf, np.inf, -1.0], 3, None),
    ([3.0, 2.0, 1.0], 0, []),
    ([3.0, 2.0, 1.0], 3, [0, 1, 2]),
])
def test_top_set_is_the_set_argsort_takes(row, d, want):
    neg = np.asarray(row, np.float32)
    got = data.top_set(neg, d)
    assert got.tolist() == sorted(np.argsort(neg)[:d].tolist())
    if want is not None:
        assert got.tolist() == want


def test_no_users_by_items_array_is_allocated_for_lists(tmp_path,
                                                        monkeypatch):
    ds = _block(num_users=2000, num_items=2500, num_interactions=40000,
                layout="lists")
    monkeypatch.setattr(data, "BLOCK_VALUES", 8 * ds["num_items"])
    tracemalloc.start()
    try:
        train_l, test_l = data.dataset(ds, tmp_path)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert isinstance(train_l, data.CSR) and isinstance(test_l, data.CSR)
    assert train_l.shape == (2000, 2500)
    assert peak < ds["num_users"] * ds["num_items"]


def test_the_cache_holds_the_lists_for_both_layouts(tmp_path):
    ds = _block(layout="lists")
    first = data.dataset(ds, tmp_path)
    (cached,) = tmp_path.glob("*.npz")
    again = data.dataset(ds, tmp_path)
    for a, b in zip(first, again):
        assert np.array_equal(a.indptr, b.indptr)
        assert np.array_equal(a.indices, b.indices)
    dense = data.dataset(dict(ds, layout="dense"), tmp_path)
    assert [p.name for p in tmp_path.glob("*.npz")] == [cached.name]
    assert [d.dtype for d in dense] == [np.uint8, np.uint8]
    for a, d in zip(first, dense):
        assert np.array_equal(data.densify(a), d)


def test_an_unknown_layout_is_an_error():
    with pytest.raises(ValueError, match="lists"):
        data.layout({"layout": "coo"})
    assert data.layout({}) == "dense"


def _tiny_config(layout):
    with open(ROOT / "bench" / "configs" / "fcf-lastfm.json") as f:
        cfg = json.load(f)
    cfg["num_factors"] = 4
    cfg["data"] = dict(cfg["data"], name="tiny", num_users=60,
                       num_items=300, num_interactions=1800, layout=layout)
    cfg["theta"] = 10
    cfg["eval"] = {"every": 5, "users": 20, "top_n": 10}
    return cfg


def _mix(strategy, **kw):
    with open(ROOT / "bench" / "traffic" / f"train.{strategy}.json") as f:
        return dict(json.load(f), **kw)


@pytest.mark.parametrize("strategy", ["bts", "full"])
def test_reference_from_lists_is_bit_equal_to_dense(tmp_path, strategy):
    cfg = _tiny_config("lists")
    lists, _ = data.dataset(cfg["data"], tmp_path)
    dense = jnp.asarray(data.densify(lists), jnp.float32)
    rcfg = reference.ref_round_config(cfg, _mix(strategy),
                                      cfg["data"]["num_items"])
    a = reference.run_training(rcfg, dense, SEED, 25)
    b = reference.run_training(rcfg, lists, SEED, 25)
    assert a.keys() == b.keys()
    for k in a:
        assert a[k].dtype == b[k].dtype and np.array_equal(a[k], b[k]), k
    assert not np.array_equal(a["q"], a["q0"])


@pytest.mark.parametrize("alt", [{"fault": "half_cohort"},
                                 {"fault": "unchanged"},
                                 {"dtype": jnp.bfloat16}],
                         ids=["half_cohort", "unchanged", "bfloat16"])
def test_reference_faults_from_lists_fail_the_committed_limits(tmp_path, alt):
    """What ``bench.calibrate`` reads for a cell's upper readings, from the
    lists of a tiny cell, judged by the committed limits."""
    cfg = _tiny_config("lists")
    lists, _ = data.dataset(cfg["data"], tmp_path)
    rcfg = reference.ref_round_config(cfg, _mix("bts"),
                                      cfg["data"]["num_items"])
    limits = compare.load_limits(ROOT, "lastfm.train.bts")
    ref = reference.run_training(rcfg, lists, SEED, 25)
    again = reference.run_training(rcfg, lists, SEED, 25)
    assert compare.judge(compare.training_numbers(again, ref, "bts"),
                         limits)["ok"]
    bad = reference.run_training(rcfg, lists, SEED, 25, **alt)
    assert not compare.judge(compare.training_numbers(bad, ref, "bts"),
                             limits)["ok"]


@pytest.fixture
def lists_program(monkeypatch):
    """The program as it will take lists: the stub lays each CSR triple out
    dense and runs the real loop on it. Records what it was handed."""
    import repro.federated as fed

    real = fed.run_fcf_simulation
    handed = []

    def program(train_x, test_x, sim_cfg):
        handed.append((train_x, test_x))
        return real(jnp.asarray(data.densify(train_x), jnp.float32),
                    jnp.asarray(data.densify(test_x), jnp.float32), sim_cfg)

    monkeypatch.setattr(fed, "run_fcf_simulation", program)
    return handed


@pytest.fixture
def lists_cell(tmp_path, monkeypatch):
    monkeypatch.setattr(train, "CACHE_DIR", tmp_path)
    limits = compare.load_limits(ROOT, "lastfm.train.bts")
    monkeypatch.setattr(compare, "load_limits", lambda root, name: limits)
    return spec.Cell("tiny.train.bts", 1, "tiny", _tiny_config("lists"),
                     "train.bts", _mix("bts", max_rounds=100), E2E_TRAIN, [])


def _train(cell, seed=SEED):
    args = SimpleNamespace(seed=seed, seconds=0.2, trace=0)
    return train.run(args, cell, jax, jax.devices(),
                     device.CompileCounter(jax), time.perf_counter(),
                     lambda msg: None)


def test_a_sound_lists_cell_is_correct(lists_cell, lists_program):
    out = _train(lists_cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    (train_x, test_x), = lists_program
    for x in (train_x, test_x):
        assert isinstance(x, data.CSR) and x.shape == (60, 300)


def _unchanged(monkeypatch, cell):
    import repro.federated.simulation as sim

    monkeypatch.setattr(sim, "server_round_step",
                        lambda state, cohort_x, **kw: (state, None))


def _half_cohort(monkeypatch, cell):
    from repro.kernels import ops

    full = ops.fcf_item_gradients

    def half_mean(q, p, x, **kw):
        h = p.shape[0] // 2
        return 2.0 * full(q, p[:h], x[:h], **kw)

    monkeypatch.setattr(ops, "fcf_item_gradients", half_mean)


def _bfloat16_control(monkeypatch, cell):
    cfg = cell.config
    rcfg = reference.ref_round_config(cfg, cell.traffic,
                                      cfg["data"]["num_items"])

    def control(_state):
        x, _ = train.device_data(jax, cfg)
        return reference.run_training(rcfg, x, SEED, cfg["eval"]["every"],
                                      dtype=jnp.bfloat16)

    monkeypatch.setattr(train, "program_state", control)


@pytest.mark.parametrize("plant", [_unchanged, _half_cohort,
                                   _bfloat16_control],
                         ids=["unchanged", "half_cohort", "bfloat16"])
def test_a_broken_lists_cell_is_caught(lists_cell, lists_program,
                                       monkeypatch, plant):
    plant(monkeypatch, lists_cell)
    out = _train(lists_cell)
    assert not out["correct"]


def test_precision_at_10_is_the_same_from_lists_and_dense(tmp_path):
    from repro.compress import CodecConfig
    from repro.serve import ServingEngine, ServingModel

    cfg = _tiny_config("lists")
    tr, te = data.dataset(cfg["data"], tmp_path)
    q = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (300, 4))
    engine = ServingEngine(ServingModel.from_dense(CodecConfig(name="int8"),
                                                   q))
    state = SimpleNamespace(q=q)
    dense = [jnp.asarray(data.densify(x), jnp.float32) for x in (tr, te)]
    from_lists = train.precision_at_10(engine, state, tr, te, users=40)
    from_dense = train.precision_at_10(engine, state, *dense, users=40)
    assert from_lists == from_dense
    assert 0.0 < from_dense < 1.0


def test_a_lists_cell_added_as_new_files_only_hands_the_program_csr(
        tmp_path, monkeypatch):
    """As ``test_bench_spec.test_a_cell_added_as_new_files_only``: a lists
    configuration and its cell arrive as new files and entries, and the
    driver hands the program one CSR triple per split."""
    import repro.federated as fed

    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    bench = spec.load_benchmark()
    config = _tiny_config("lists")
    config["name"] = "fcf-lists"
    (root / "bench/configs/fcf-lists.json").write_text(json.dumps(config))
    new = dict(bench)
    new["configs"] = bench["configs"] + [{
        "name": "fcf-lists", "source": "https://grouplens.org/datasets/",
        "file": "bench/configs/fcf-lists.json", "reduced": [],
        "why": "per-user item lists"}]
    new["workloads"] = bench["workloads"] + [{
        "name": "lists.train.bts", "config": "fcf-lists",
        "traffic": "train.bts", "chips": 1, "why": "added by files"}]
    new["end_to_end"] = [dict(m, workloads=m["workloads"]
                              + ["lists.train.bts"])
                         if m["name"] == "rounds_per_s" else m
                         for m in bench["end_to_end"]]
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    cell = spec.find_cell("lists.train.bts", root=root)
    monkeypatch.setattr(train, "CACHE_DIR", tmp_path / "cache")

    class Handed(Exception):
        pass

    handed = []

    def program(train_x, test_x, sim_cfg):
        handed.append((train_x, test_x, sim_cfg))
        raise Handed

    monkeypatch.setattr(fed, "run_fcf_simulation", program)
    with pytest.raises(Handed):
        _train(cell)
    (train_x, test_x, sim_cfg), = handed
    want = data.dataset(config["data"], tmp_path / "cache" / "data")
    for x, w in zip((train_x, test_x), want):
        indptr, indices, shape = x
        assert isinstance(indptr, np.ndarray) and indptr.dtype == np.int64
        assert isinstance(indices, np.ndarray) and indices.dtype == np.int32
        assert shape == (60, 300) and indptr.shape == (61,)
        assert np.array_equal(indptr, w.indptr)
        assert np.array_equal(indices, w.indices)
    assert sim_cfg.theta == 10


def test_the_serving_driver_refuses_a_lists_config():
    cfg = _tiny_config("lists")
    cell = spec.Cell("tiny.serve.open", 1, "tiny", cfg, "serve.open", {},
                     [], [])
    with pytest.raises(ValueError, match="dense configuration only"):
        serve.build(jax, cell, SEED)


def test_the_ml25m_configuration_states_lists_at_its_source_sizes():
    def load(name):
        with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
            return json.load(f)

    cfg, lastfm = load("fcf-ml25m"), load("fcf-lastfm")
    ds = cfg["data"]
    assert (ds["num_users"], ds["num_items"], ds["num_interactions"],
            ds["min_degree"]) == (162541, 62423, 25000095, 20)
    assert data.layout(ds) == "lists" and cfg["theta"] == 1000
    for key in ("num_factors", "wire", "moments", "bandit", "server_adam",
                "eval", "l2", "alpha", "init_scale"):
        assert cfg[key] == lastfm[key], key
    for key in ("theta", "data"):
        assert key in cfg["assumed"]
