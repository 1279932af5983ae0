"""``correct`` comes out false when the timed path is broken underneath.

A tiny cell of each kind runs the rest of a real run on the CPU (the look
for a chip skipped): training through ``run_fcf_simulation`` and serving
through ``ServingEngine.recommend``, compared with the plain reference and
held to the committed limits of the full-size cells. Then each fault the
cell can have is planted in the program, and the control (the reference in
the next precision down) is put in the program's place.
"""
import json
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import compare, device, reference, serve, spec, train
from bench.harness.device import ROOT

E2E_TRAIN = [{"name": "setup_s", "unit": "s"},
             {"name": "rounds_per_s", "unit": "rounds/s"}]
E2E_SERVE = [{"name": "setup_s", "unit": "s"},
             {"name": "serve_p50_ms", "unit": "ms"},
             {"name": "serve_p95_ms", "unit": "ms"}]


def _config():
    with open(ROOT / "bench" / "configs" / "fcf-lastfm.json") as f:
        cfg = json.load(f)
    cfg["num_factors"] = 4
    return cfg


@pytest.fixture
def train_cell(tmp_path, monkeypatch):
    monkeypatch.setattr(train, "CACHE_DIR", tmp_path)
    cfg = _config()
    cfg["data"] = dict(cfg["data"], name="tiny", num_users=60,
                       num_items=300, num_interactions=1800)
    cfg["theta"] = 10
    cfg["eval"] = {"every": 5, "users": 20, "top_n": 10}
    with open(ROOT / "bench" / "traffic" / "train.bts.json") as f:
        mix = dict(json.load(f), max_rounds=100)
    limits = compare.load_limits(ROOT, "lastfm.train.bts")
    monkeypatch.setattr(compare, "load_limits", lambda root, name: limits)
    return spec.Cell("tiny.train.bts", 1, "tiny", cfg, "train.bts", mix,
                     E2E_TRAIN, [])


def _train(cell, seed=2147483901):
    args = SimpleNamespace(seed=seed, seconds=0.2, trace=0)
    return train.run(args, cell, jax, jax.devices(),
                     device.CompileCounter(jax), time.perf_counter(),
                     lambda msg: None)


def test_sound_training_is_correct(train_cell):
    out = _train(train_cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_a_round_that_returns_its_state_unchanged_is_caught(
        train_cell, monkeypatch):
    import repro.federated.simulation as sim

    monkeypatch.setattr(sim, "server_round_step",
                        lambda state, cohort_x, **kw: (state, None))
    out = _train(train_cell)
    assert not out["correct"]
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_the_cohort_left_out_is_caught(train_cell, monkeypatch):
    from repro.kernels import ops

    full = ops.fcf_item_gradients

    def half_mean(q, p, x, **kw):
        h = p.shape[0] // 2
        return 2.0 * full(q, p[:h], x[:h], **kw)

    monkeypatch.setattr(ops, "fcf_item_gradients", half_mean)
    out = _train(train_cell)
    assert not out["correct"]


def test_bfloat16_reference_in_the_programs_place_is_caught(
        train_cell, monkeypatch):
    cfg = train_cell.config
    rcfg = reference.ref_round_config(cfg, train_cell.traffic,
                                      cfg["data"]["num_items"])
    seed = 2147483901

    def control(_state):
        x, _ = train.device_data(jax, cfg)
        return reference.run_training(rcfg, x, seed, cfg["eval"]["every"],
                                      dtype=jnp.bfloat16)

    monkeypatch.setattr(train, "program_state", control)
    out = _train(train_cell, seed)
    assert not out["correct"]


@pytest.fixture
def serve_cell(tmp_path, monkeypatch):
    # the Pallas scorer in interpret mode: the jitted path the chip runs
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    monkeypatch.setattr(serve, "CACHE_DIR", tmp_path)
    cfg = _config()
    cfg["data"] = dict(cfg["data"], name="tiny", num_users=300,
                       num_items=500, num_interactions=6000)
    with open(ROOT / "bench" / "traffic" / "serve.open.json") as f:
        mix = dict(json.load(f), rate_per_s=40, batch_max=20,
                   buckets=[8, 64], block_m=128, check_sample=10)
    limits = compare.load_limits(ROOT, "lastfm.serve.open")
    monkeypatch.setattr(compare, "load_limits", lambda root, name: limits)
    return spec.Cell("tiny.serve.open", 1, "tiny", cfg, "serve.open", mix,
                     E2E_SERVE, [])


def _serve(cell):
    args = SimpleNamespace(seed=2147483902, seconds=1.0, trace=0)
    return serve.run(args, cell, jax, jax.devices(),
                     device.CompileCounter(jax), time.perf_counter(),
                     lambda msg: None)


def test_sound_serving_is_correct(serve_cell):
    out = _serve(serve_cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 40 and out["failed"] == 0


def test_an_answer_altered_where_it_is_produced_is_caught(
        serve_cell, monkeypatch):
    from repro.serve import ServingEngine

    honest = ServingEngine.recommend
    m = serve_cell.config["data"]["num_items"]

    def altered(self, p, top_n=None, train_mask=None, admitted_at=None):
        vals, ids = honest(self, p, top_n=top_n, train_mask=train_mask)
        return vals, ids.at[0, 0].set((ids[0, 0] + 1) % m)

    monkeypatch.setattr(ServingEngine, "recommend", altered)
    out = _serve(serve_cell)
    assert not out["correct"]


def test_the_programs_int4_path_in_place_of_int8_is_caught(
        serve_cell, monkeypatch):
    honest_build = serve.build

    def build(jax_, cell, seed):
        _, table, p, seen = honest_build(jax_, cell, seed)
        return serve.engine_for(table, "int4", cell.traffic), table, p, seen

    monkeypatch.setattr(serve, "build", build)
    out = _serve(serve_cell)
    assert not out["correct"]
    assert out["checks"]["rank_gap"]["value"] > 0.0
    assert np.isfinite(out["checks"]["rank_gap"]["value"])
