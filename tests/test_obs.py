"""Observability layer: the disabled-path bit-parity contract, the round
telemetry stream, span tracing, histograms and the serving /metrics surface.

The hard contract (repro.obs docstring): with ``obs=None`` or
``ObsConfig(enabled=False)`` every telemetry hook is skipped at
Python/trace time, so trajectories are BIT-identical to a build without
the obs package — checked here for the scan, python and async engines
in-process and for the D=8 sharded engine in a fake-device subprocess.
With ``enabled=True`` the trajectory must STILL be bit-identical (the
telemetry ops are pure observers) while the sink receives one schema-valid
round event per (rate-limited) round whose traced regret aggregates match
the host-side ``core.regret.RegretTracker`` fold.
"""
import json
import os
import pathlib
import subprocess
import sys
import threading
from dataclasses import replace

import numpy as np
import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
SRC = str(REPO_ROOT / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

from _hypothesis_compat import given, settings, st  # noqa: E402

from repro.federated.simulation import FLSimConfig, run_fcf_simulation  # noqa: E402
from repro.launch.mesh import fake_cpu_devices_env  # noqa: E402
from repro.obs import (  # noqa: E402
    InMemorySink, LatencyHistogram, ObsConfig, TELEMETRY_FIELDS, Tracer,
    install_tracer, rows_to_events, span, validate_round_event,
)
from repro.obs.prom import parse, validate_text  # noqa: E402
from repro.obs.trace import (  # noqa: E402
    NullTracer, active_tracer, recording, validate_span_event,
)

BACKENDS = ("scan", "python", "async")


def _mini_data(seed=0, users=60, items=80):
    rng = np.random.default_rng(seed)
    train = (rng.random((users, items)) < 0.15).astype(np.float32)
    test = (rng.random((users, items)) < 0.05).astype(np.float32)
    return train, test


def _cfg(backend, **kw):
    base = dict(strategy="bts", keep_fraction=0.25, rounds=6, theta=10,
                eval_every=3, eval_users=40, seed=0, codec="int8",
                record_selections=True)
    if backend == "async":
        base["max_staleness"] = 2
    base["backend"] = backend if backend != "scan" else "scan"
    base.update(kw)
    return FLSimConfig(**base)


def _assert_bitwise(tag, a, b):
    np.testing.assert_array_equal(a.selections, b.selections,
                                  err_msg=f"{tag}: selections")
    np.testing.assert_array_equal(a.rewards, b.rewards,
                                  err_msg=f"{tag}: rewards")
    np.testing.assert_array_equal(np.asarray(a.server_state.q),
                                  np.asarray(b.server_state.q),
                                  err_msg=f"{tag}: Q")
    np.testing.assert_array_equal(np.asarray(a.server_state.opt.m),
                                  np.asarray(b.server_state.opt.m),
                                  err_msg=f"{tag}: adam m")
    assert float(a.server_state.bytes_down) == \
        float(b.server_state.bytes_down), f"{tag}: bytes_down"
    assert float(a.server_state.bytes_up) == \
        float(b.server_state.bytes_up), f"{tag}: bytes_up"
    assert a.history.series("f1") == b.history.series("f1"), \
        f"{tag}: f1 trajectory"


# --------------------------------------------------------------------- #
# the bit-parity contract (scan / python / async, in-process)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", BACKENDS)
def test_disabled_obs_is_bit_identical(backend):
    """obs=None and ObsConfig(enabled=False) must produce the exact same
    trajectory — the disabled path adds zero ops by construction."""
    train, test = _mini_data()
    cfg = _cfg(backend)
    base = run_fcf_simulation(train, test, cfg)
    off = run_fcf_simulation(
        train, test, replace(cfg, obs=ObsConfig(enabled=False)))
    _assert_bitwise(f"{backend}/disabled", base, off)


@pytest.mark.parametrize("backend", BACKENDS)
def test_enabled_obs_preserves_trajectory_and_emits(backend):
    """Telemetry ops are pure observers: enabling them must not perturb
    the round math, and every round must land in the sink as one
    schema-valid event with monotone t and non-decreasing cum_regret."""
    train, test = _mini_data()
    cfg = _cfg(backend)
    base = run_fcf_simulation(train, test, cfg)
    sink = InMemorySink()
    on = run_fcf_simulation(
        train, test, replace(cfg, obs=ObsConfig(enabled=True, sink=sink)))
    _assert_bitwise(f"{backend}/enabled", base, on)

    events = sink.events
    assert len(events) == cfg.rounds
    for e in events:
        assert validate_round_event(e) == [], validate_round_event(e)
    ts = [e["t"] for e in events]
    assert ts == list(range(1, cfg.rounds + 1))
    cum = [e["cum_regret"] for e in events]
    assert all(b >= a for a, b in zip(cum, cum[1:])), cum
    assert all(e["collective_bytes"] == 0.0 for e in events)  # off-mesh
    assert all(e["bytes_down"] > 0 and e["bytes_up"] > 0 for e in events)
    if backend == "async":
        for e in events:
            assert 0 <= e["staleness"] <= cfg.max_staleness
            np.testing.assert_allclose(
                e["step_weight"],
                cfg.staleness_discount ** e["staleness"], rtol=1e-6)
    else:
        assert all(e["staleness"] == 0 and e["step_weight"] == 1.0
                   for e in events)


def test_telemetry_every_rate_limit():
    """telemetry_every=4 over 8 rounds -> events at t=1 (always), 4, 8."""
    train, test = _mini_data()
    sink = InMemorySink()
    cfg = _cfg("scan", rounds=8,
               obs=ObsConfig(enabled=True, sink=sink, telemetry_every=4))
    run_fcf_simulation(train, test, cfg)
    assert [e["t"] for e in sink.events] == [1, 4, 8]


def test_traced_regret_matches_host_tracker():
    """The in-scan regret fold must reproduce core.regret.RegretTracker
    (the float64 host reference) on the same selections/rewards stream."""
    from repro.core.regret import RegretTracker

    train, test = _mini_data()
    sink = InMemorySink()
    cfg = _cfg("scan", rounds=8, obs=ObsConfig(enabled=True, sink=sink))
    result = run_fcf_simulation(train, test, cfg)

    tracker = RegretTracker(num_arms=train.shape[1])
    for idx, rew in zip(result.selections, result.rewards):
        tracker.record(idx, rew)
    traced_cum = [e["cum_regret"] for e in sink.events]
    np.testing.assert_allclose(traced_cum, tracker.cumulative,
                               rtol=1e-4, atol=1e-5)
    traced_mean = [e["reward_mean"] for e in sink.events]
    np.testing.assert_allclose(traced_mean, tracker.per_round_mean,
                               rtol=1e-5, atol=1e-6)


def test_seed_sweep_rejects_enabled_obs():
    from repro.federated.simulation import run_seed_sweep

    train, test = _mini_data()
    cfg = _cfg("scan", obs=ObsConfig(enabled=True))
    with pytest.raises(ValueError, match="obs"):
        run_seed_sweep(train, test, cfg, seeds=(0, 1))


# --------------------------------------------------------------------- #
# D=8 sharded engine (fake-device subprocess, one jax init)
# --------------------------------------------------------------------- #
_SHARD_SCRIPT = r"""
from dataclasses import replace
import numpy as np
from repro.federated.simulation import FLSimConfig, run_fcf_simulation
from repro.obs import InMemorySink, ObsConfig, validate_round_event

rng = np.random.default_rng(0)
train = (rng.random((60, 80)) < 0.15).astype(np.float32)
test = (rng.random((60, 80)) < 0.05).astype(np.float32)

cfg = FLSimConfig(strategy="bts", keep_fraction=0.25, rounds=6, theta=10,
                  eval_every=3, eval_users=40, seed=0, codec="int8",
                  record_selections=True, backend="shard", mesh_shards=8)

base = run_fcf_simulation(train, test, cfg)
off = run_fcf_simulation(train, test,
                         replace(cfg, obs=ObsConfig(enabled=False)))
sink = InMemorySink()
on = run_fcf_simulation(train, test,
                        replace(cfg, obs=ObsConfig(enabled=True, sink=sink)))

for tag, other in (("disabled", off), ("enabled", on)):
    np.testing.assert_array_equal(base.selections, other.selections,
                                  err_msg=f"{tag}: selections")
    np.testing.assert_array_equal(np.asarray(base.server_state.q),
                                  np.asarray(other.server_state.q),
                                  err_msg=f"{tag}: Q")
    assert base.history.series("f1") == other.history.series("f1"), tag

events = sink.events
assert len(events) == cfg.rounds, len(events)
assert [e["t"] for e in events] == list(range(1, cfg.rounds + 1))
for e in events:
    assert validate_round_event(e) == [], validate_round_event(e)
    # the sharded engine's psum-reduced cross-device byte counter: D shards
    # each move (downlink wire + m_s*k*4 fp32 grad rows) over the mesh
    assert e["collective_bytes"] > 0, e
cum = [e["cum_regret"] for e in events]
assert all(b >= a for a, b in zip(cum, cum[1:])), cum

print("SHARD_OBS_OK rounds=%d" % len(events))
"""


@pytest.mark.subprocess
def test_shard_backend_obs_parity_and_collectives():
    """D=8 sharded engine: disabled AND enabled obs are bit-identical to
    the plain shard run; the telemetry stream reports psum-reduced
    collective bytes > 0 (it runs on a real 8-device mesh)."""
    env = fake_cpu_devices_env(8)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _SHARD_SCRIPT],
        capture_output=True, text=True, env=env, cwd=str(REPO_ROOT),
        timeout=1200,
    )
    assert proc.returncode == 0, (
        f"shard obs subprocess failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}")
    assert "SHARD_OBS_OK rounds=6" in proc.stdout


# --------------------------------------------------------------------- #
# telemetry row/event plumbing
# --------------------------------------------------------------------- #
def test_rows_to_events_shapes_and_rate_limit():
    row = np.arange(1, len(TELEMETRY_FIELDS) + 1, dtype=np.float32)
    (event,) = rows_to_events(row)                       # single row ok
    assert event["type"] == "round" and event["t"] == 1
    rows = np.stack([row * 0 + np.arange(len(TELEMETRY_FIELDS))
                     for _ in range(3)])
    rows[:, 0] = [1, 2, 3]                               # t column
    assert [e["t"] for e in rows_to_events(rows, every=3)] == [1, 3]
    with pytest.raises(ValueError, match="fields"):
        rows_to_events(np.zeros((2, 3)))


def test_validate_round_event_rejects_bad_events():
    good = rows_to_events(
        np.arange(1, len(TELEMETRY_FIELDS) + 1, dtype=np.float32))[0]
    assert validate_round_event(good) == []
    assert validate_round_event({"type": "round"})       # missing fields
    bad_type = dict(good, type="span")
    assert any("type" in e for e in validate_round_event(bad_type))
    neg = dict(good, bytes_down=-1.0)
    assert any("bytes_down" in e for e in validate_round_event(neg))
    frac_t = dict(good, t=1.5)
    assert any("integral" in e for e in validate_round_event(frac_t))


# --------------------------------------------------------------------- #
# span tracing
# --------------------------------------------------------------------- #
def test_tracer_nested_spans_schema_and_restore(tmp_path):
    tracer = Tracer()                                    # in-memory
    prev = install_tracer(tracer)
    try:
        with span("outer", phase="train"):
            with span("inner"):
                pass
    finally:
        restored = install_tracer(prev)
    assert restored is tracer and active_tracer() is prev

    # spans close inner-first; nesting is recorded as depth + parent name
    inner, outer = tracer.events
    assert (inner["name"], inner["depth"], inner["parent"]) == \
        ("inner", 1, "outer")
    assert (outer["name"], outer["depth"], outer["parent"]) == \
        ("outer", 0, None)
    assert outer["attrs"] == {"phase": "train"}
    assert outer["dur"] >= inner["dur"] >= 0
    for e in tracer.events:
        assert validate_span_event(e) == [], validate_span_event(e)

    # file-backed tracer writes parseable JSONL
    path = tmp_path / "trace.jsonl"
    jt = Tracer(str(path))
    with jt.span("write"):
        pass
    jt.close()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == 1 and validate_span_event(lines[0]) == []


def test_lists_layout_span_carries_users_ids_and_cap():
    """Per-user item lists are laid out on the device in set-up under the
    span ``lists.layout``: the users, the train ids, the most ids the walk
    of one cohort can meet (the Theta largest degrees together), the ids in
    one row of the layout (the walk reads whole rows), and the most rows one
    cohort's lists touch."""
    train, test = _mini_data()

    def csr(x):
        rows, cols = np.nonzero(x)
        indptr = np.zeros(x.shape[0] + 1, np.int64)
        np.cumsum(np.bincount(rows, minlength=x.shape[0]), out=indptr[1:])
        return indptr, cols.astype(np.int32), x.shape

    degrees = np.sort(train.sum(axis=1))
    tracer = Tracer()
    prev = install_tracer(tracer)
    try:
        run_fcf_simulation(csr(train), csr(test),
                           _cfg("scan", rounds=3, eval_every=3))
    finally:
        install_tracer(prev)
    (event,) = [e for e in tracer.events if e["name"] == "lists.layout"]
    indptr = csr(train)[0]
    rows = (indptr[1:] - 1) // 8 - indptr[:-1] // 8 + 1   # no empty list
    assert event["attrs"] == {
        "users": 60, "ids": int(train.sum()), "cap": int(degrees[-10:].sum()),
        "chunk": 8,                       # the floor: median degree < 64
        "cap_chunks": int(np.sort(rows)[-10:].sum())}
    assert validate_span_event(event) == []


def test_null_tracer_span_is_shared_noop():
    """The default tracer hands back the bare profiler annotation of the
    span's name — one enter and exit with no profiler running — and
    records nothing."""
    from jax.profiler import TraceAnnotation

    nt = NullTracer()
    assert type(nt.span("a")) is type(nt.span("b", attr=1)) \
        is TraceAnnotation
    with nt.span("a"):
        pass                                             # no-op, no error
    assert active_tracer() is not nt and not recording()


PROGRAM_SPANS = ("train_chunk", "eval", "publish", "publish_snapshot",
                 "publish.encode", "publish.install")
ROUND_SCOPES = ("fl_select", "fl_downlink", "fl_gather", "fl_solve",
                "fl_grad", "fl_commit")


def _host_span_names(profile_dir):
    from jax.profiler import ProfileData

    (path,) = pathlib.Path(profile_dir).glob("**/*.xplane.pb")
    host = ProfileData.from_file(str(path)).find_plane_with_name("/host:CPU")
    return [e.name for line in host.lines for e in line.events]


@pytest.mark.parametrize("jsonl", [False, True], ids=["profiler", "jsonl"])
def test_spans_land_on_the_profiler_trace(tmp_path, jsonl):
    """Every program span is a profiler annotation: a ``jax.profiler``
    session around the loop finds them on the host plane, with or without
    a JSONL tracer installed."""
    import jax
    from repro.compress import CodecConfig
    from repro.serve import ServingEngine, ServingModel

    train, test = _mini_data()
    engine = ServingEngine(ServingModel.from_dense(
        CodecConfig(name="int8"), np.zeros((train.shape[1], 25), np.float32)))
    cfg = _cfg("scan", snapshot_hook=engine.publisher())
    if jsonl:
        cfg = replace(cfg, obs=ObsConfig(
            enabled=True, trace_path=str(tmp_path / "spans.jsonl")))
    with jax.profiler.trace(str(tmp_path / "prof")):
        run_fcf_simulation(train, test, cfg)
    names = _host_span_names(tmp_path / "prof")
    chunks = cfg.rounds // cfg.eval_every
    for name in PROGRAM_SPANS:
        assert names.count(name) == chunks, (name, names.count(name))
    if jsonl:
        cfg.obs.close()
        events = [json.loads(line) for line in
                  (tmp_path / "spans.jsonl").read_text().splitlines()]
        assert sorted({e["name"] for e in events}) == sorted(PROGRAM_SPANS)
        nested = {e["name"]: e["parent"] for e in events}
        assert nested["publish.encode"] == "publish_snapshot"
        assert nested["publish.install"] == "publish_snapshot"


@pytest.mark.parametrize("jsonl", [False, True], ids=["profiler", "jsonl"])
def test_train_chunk_span_syncs_only_when_recorded(monkeypatch, jsonl):
    """Under a JSONL tracer the ``train_chunk`` span closes on the chunk's
    finished state, so its duration is the chunk's work; with only the
    profiler it adds no sync."""
    import jax
    from repro.federated import simulation

    synced = []
    real = jax.block_until_ready

    def spy(x):
        synced.append(active_tracer()._stack()[-1]
                      if recording() else None)
        return real(x)

    monkeypatch.setattr(simulation.jax, "block_until_ready", spy)
    train, test = _mini_data()
    tracer = Tracer() if jsonl else None
    prev = install_tracer(tracer)
    try:
        run_fcf_simulation(train, test, _cfg("scan"))
    finally:
        install_tracer(prev)
    chunks = 6 // 3
    assert synced == (["train_chunk"] * chunks if jsonl else [])
    if jsonl:
        assert [e["name"] for e in tracer.events].count("train_chunk") \
            == chunks


@pytest.mark.parametrize("backend", ["scan", "async"])
def test_round_phases_are_named_in_the_compiled_chunk(backend):
    """Each phase of the compiled round carries its ``fl_*`` scope in the
    chunk program's debug info (the name stack the device trace shows)."""
    import jax
    import jax.numpy as jnp
    from repro.federated.simulation import _build, _make_round_fn

    train, test = _mini_data()
    cfg = _cfg(backend)
    train_j = jnp.asarray(train)
    setup = _build(train_j, jnp.asarray(test), cfg)
    cohorts = jnp.asarray(setup.cohorts[:3])
    is_async = backend == "async"
    round_fn = _make_round_fn(train_j, setup, blocks=1, is_async=is_async,
                              telemetry=False)
    if is_async:
        xs = (cohorts, jnp.asarray(setup.staleness[:3], jnp.int32))
    else:
        xs = cohorts

    def scan_chunk(st, xs):
        def body(s, x):
            args = x if isinstance(x, tuple) else (x,)
            return round_fn(s, *args)[0], None
        return jax.lax.scan(body, st, xs)

    text = jax.jit(scan_chunk).lower(setup.state0, xs).as_text(
        debug_info=True)
    missing = [name for name in ROUND_SCOPES if name not in text]
    assert not missing, missing


# --------------------------------------------------------------------- #
# latency histogram properties
# --------------------------------------------------------------------- #
@settings(deadline=None, max_examples=20)
@given(n=st.integers(min_value=1, max_value=200),
       scale=st.floats(min_value=1e-5, max_value=10.0))
def test_property_histogram_quantiles_bounded_and_monotone(n, scale):
    rng = np.random.default_rng(n * 7919 + int(scale * 100))
    vals = scale * rng.random(n)
    h = LatencyHistogram.from_values(vals)
    assert h.total == n
    qs = h.quantiles([0.0, 0.25, 0.5, 0.9, 0.99, 1.0])
    assert all(b >= a for a, b in zip(qs, qs[1:])), qs
    assert qs[0] >= float(vals.min()) - 1e-12
    assert qs[-1] <= float(vals.max()) + 1e-12
    # bucket resolution: every quantile lies within one geometric bucket
    # (~9% relative) of an actually-recorded value — the HDR guarantee.
    # (np.median-style midpoint interpolation is a DIFFERENT definition and
    # can sit a whole order statistic away at small n; the shared-definition
    # point of obs.hist is exactly that all reporters agree on this one.)
    for qv in qs:
        nearest = float(np.min(np.abs(vals - qv)))
        assert nearest <= qv * (2 ** (1 / 8) - 1) + 2 * h.min_value, \
            (qv, nearest)


@settings(deadline=None, max_examples=20)
@given(na=st.integers(min_value=0, max_value=100),
       nb=st.integers(min_value=0, max_value=100))
def test_property_histogram_merge_is_exact(na, nb):
    rng = np.random.default_rng(na * 1000 + nb)
    a_vals, b_vals = rng.random(na) * 0.1, rng.random(nb) * 10.0
    a = LatencyHistogram.from_values(a_vals)
    b = LatencyHistogram.from_values(b_vals)
    merged = a.merge(b)
    both = LatencyHistogram.from_values(np.concatenate([a_vals, b_vals]))
    np.testing.assert_array_equal(merged.counts, both.counts)
    assert merged.total == na + nb
    np.testing.assert_allclose(merged.sum, both.sum, rtol=1e-12)
    if na + nb:
        assert merged.quantile(0.5) == both.quantile(0.5)
    # merge leaves the operands untouched
    assert a.total == na and b.total == nb


def test_histogram_edge_cases():
    h = LatencyHistogram()
    assert h.total == 0 and np.isnan(h.quantile(0.5))
    with pytest.raises(ValueError):
        h.record(-1.0)
    with pytest.raises(ValueError):
        h.record(float("inf"))
    with pytest.raises(ValueError):
        h.quantile(1.5)
    h.record(0.01)
    assert h.quantile(0.0) == h.quantile(1.0) == 0.01    # exact envelope
    other = LatencyHistogram(min_value=1e-3)
    with pytest.raises(ValueError, match="geometry"):
        h.merge(other)
    # out-of-range values land in the first / overflow buckets
    h2 = LatencyHistogram.from_values([1e-9, 5e3])
    assert h2.counts[0] == 1 and h2.counts[-1] == 1


# --------------------------------------------------------------------- #
# MetricLogger on the obs sinks (satellite regression)
# --------------------------------------------------------------------- #
def test_metric_logger_csv_stable_columns_and_restval(tmp_path):
    """Heterogeneous rows: column order is a function of the key SET only
    (front keys, then sorted), and missing cells are explicit ''."""
    import csv

    from repro.utils.logging import MetricLogger

    path = tmp_path / "m.csv"
    log = MetricLogger(str(path))
    log.log(1, loss=0.5)
    log.log(2, f1=0.3, precision=0.2)                    # eval-only keys
    log.log(3, loss=0.4)
    log.to_csv()
    with open(path, newline="") as f:
        reader = csv.DictReader(f)
        header = reader.fieldnames
        rows = list(reader)
    assert header == ["step", "wall_s", "f1", "loss", "precision"]
    assert rows[0]["f1"] == "" and rows[0]["loss"] == "0.5"
    assert rows[1]["loss"] == "" and rows[1]["f1"] == "0.3"
    assert [r["step"] for r in rows] == ["1", "2", "3"]

    # logging the keys in a different order yields the same header
    log2 = MetricLogger(str(tmp_path / "m2.csv"))
    log2.log(1, precision=0.2, f1=0.3)
    log2.log(2, loss=0.5)
    log2.to_csv()
    with open(tmp_path / "m2.csv", newline="") as f:
        assert csv.DictReader(f).fieldnames == header

    stream_only = type("S", (), {"emit": lambda self, e: None,
                                 "close": lambda self: None})()
    with pytest.raises(ValueError, match="events"):
        MetricLogger(sink=stream_only)


# --------------------------------------------------------------------- #
# serving /metrics surface
# --------------------------------------------------------------------- #
def _tiny_engine(obs):
    import jax.numpy as jnp

    from repro.compress import CodecConfig
    from repro.serve import ServingEngine, ServingModel

    rng = np.random.default_rng(3)
    q = jnp.asarray(0.1 * rng.standard_normal((64, 8)), jnp.float32)
    model = ServingModel.from_dense(CodecConfig(name="int8"), q)
    return ServingEngine(model, buckets=(4,), top_n=5, obs=obs)


def test_serving_metrics_parse_and_counters():
    from repro.obs.check import REQUIRED_SERVE_FAMILIES

    engine = _tiny_engine(ObsConfig(enabled=True))
    rng = np.random.default_rng(5)
    p = rng.standard_normal((4, 8)).astype(np.float32)
    for _ in range(3):
        engine.recommend(p)
    text = engine.metrics()
    assert validate_text(text, require=REQUIRED_SERVE_FAMILIES) == []
    fams = parse(text)
    assert fams["frs_serve_requests_total"]["samples"][
        "frs_serve_requests_total"][0][1] == 3.0
    assert fams["frs_serve_users_total"]["samples"][
        "frs_serve_users_total"][0][1] == 12.0
    assert fams["frs_serve_queue_depth"]["samples"][
        "frs_serve_queue_depth"][0][1] == 0.0
    hist = fams["frs_serve_latency_seconds"]["samples"]
    counts = {tuple(sorted(l.items())): v
              for l, v in hist["frs_serve_latency_seconds_count"]}
    assert sum(counts.values()) == 3.0                   # one timed chunk/req
    assert engine.latency_histogram().total == 3


def test_serving_metrics_without_obs_still_render():
    """metrics() must expose the required families even with obs off —
    latency histograms just stay empty (no timing syncs on the read path)."""
    from repro.obs.check import REQUIRED_SERVE_FAMILIES

    engine = _tiny_engine(None)
    engine.recommend(np.zeros((2, 8), np.float32))
    text = engine.metrics()
    assert validate_text(text, require=REQUIRED_SERVE_FAMILIES) == []
    assert engine.latency_histogram().total == 0
    fams = parse(text)
    assert fams["frs_serve_requests_total"]["samples"][
        "frs_serve_requests_total"][0][1] == 1.0


def test_serving_metrics_monotone_under_concurrent_readers():
    """Counters never move backwards across scrapes racing recommend()."""
    engine = _tiny_engine(ObsConfig(enabled=True))
    rng = np.random.default_rng(11)
    p = rng.standard_normal((4, 8)).astype(np.float32)
    stop = threading.Event()
    errors = []

    def scrape():
        last = -1.0
        while not stop.is_set():
            try:
                fams = parse(engine.metrics())
                cur = fams["frs_serve_requests_total"]["samples"][
                    "frs_serve_requests_total"][0][1]
            except Exception as exc:          # malformed mid-race scrape
                errors.append(exc)
                return
            if cur < last:
                errors.append(
                    AssertionError(f"requests_total {cur} < {last}"))
                return
            last = cur

    readers = [threading.Thread(target=scrape) for _ in range(2)]
    for r in readers:
        r.start()
    try:
        for _ in range(20):
            engine.recommend(p)
    finally:
        stop.set()
        for r in readers:
            r.join(timeout=30)
    assert not errors, errors
    assert engine.stats().requests == 20
