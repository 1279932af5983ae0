"""Sharded round engine: shard_map == single-device scan, bit for bit.

The tentpole contract of the sharded engine: running the FL round
data-parallel over a ("data",) device mesh (row-sharded tables, one cohort
block per device, collective payload movement, ordered-psum gradient
reduction) must reproduce the single-device ``backend="scan"`` trajectory —
selections, Q, Adam moments, byte counters — exactly, for every strategy,
with the fp32 and int8 codecs. ``cohort_shards=D`` pins the scan reference
to the same client-phase block structure (the float semantics of a round are
a function of the block structure only; see ``server_round_step``).

Multi-device CPU meshes require ``--xla_force_host_platform_device_count``
to be set before jax initializes, so the D=8 parity matrix runs in one
subprocess; single-device properties (D=1 == plain scan, config validation,
pspec rules) run in-process.
"""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
SRC = str(REPO_ROOT / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

from repro.launch.mesh import fake_cpu_devices_env  # noqa: E402

STRATEGIES = ("bts", "random", "full", "magnitude")


def _mini_data(seed=0, users=60, items=80):
    rng = np.random.default_rng(seed)
    train = (rng.random((users, items)) < 0.15).astype(np.float32)
    test = (rng.random((users, items)) < 0.05).astype(np.float32)
    return train, test


# --------------------------------------------------------------------- #
# D=8 parity matrix (subprocess with 8 fake CPU devices)
# --------------------------------------------------------------------- #
_PARITY_SCRIPT = r"""
from dataclasses import replace
import numpy as np
from repro.federated.simulation import FLSimConfig, run_fcf_simulation

rng = np.random.default_rng(0)
train = (rng.random((60, 80)) < 0.15).astype(np.float32)
test = (rng.random((60, 80)) < 0.05).astype(np.float32)

def run_pair(strategy, codec, shards):
    cfg = FLSimConfig(strategy=strategy, keep_fraction=0.25, rounds=6,
                      theta=10, eval_every=3, eval_users=40, seed=0,
                      codec=codec, record_selections=True)
    scan = run_fcf_simulation(train, test, replace(cfg, cohort_shards=shards))
    shard = run_fcf_simulation(
        train, test, replace(cfg, backend="shard", mesh_shards=shards))
    return scan, shard

def assert_bitwise(tag, scan, shard):
    np.testing.assert_array_equal(scan.selections, shard.selections,
                                  err_msg=f"{tag}: selections")
    np.testing.assert_array_equal(scan.rewards, shard.rewards,
                                  err_msg=f"{tag}: rewards")
    np.testing.assert_array_equal(np.asarray(scan.server_state.q),
                                  np.asarray(shard.server_state.q),
                                  err_msg=f"{tag}: Q")
    np.testing.assert_array_equal(np.asarray(scan.server_state.opt.m),
                                  np.asarray(shard.server_state.opt.m),
                                  err_msg=f"{tag}: adam m")
    assert float(scan.server_state.bytes_down) == \
        float(shard.server_state.bytes_down), f"{tag}: bytes_down"
    assert float(scan.server_state.bytes_up) == \
        float(shard.server_state.bytes_up), f"{tag}: bytes_up"
    assert scan.history.series("f1") == shard.history.series("f1"), \
        f"{tag}: f1 trajectory"

checked = 0
# the hard bit-parity contract: every strategy x {fp32, int8} at D=8
for strategy in ("bts", "random", "full", "magnitude"):
    for codec in ("fp32", "int8"):
        scan, shard = run_pair(strategy, codec, 8)
        assert_bitwise(f"{strategy}/{codec}/D=8", scan, shard)
        checked += 1

# D=1 sharded == the untouched default scan engine, bit for bit
for codec in ("fp32", "int8"):
    scan, shard = run_pair("bts", codec, 1)
    assert_bitwise(f"bts/{codec}/D=1", scan, shard)
    checked += 1

# int4/topk: selections identical; trajectories agree to contraction ulps
# (XLA:CPU FMA-choice inside their dequant fusions — see server_round_step)
for codec in ("int4", "topk"):
    scan, shard = run_pair("bts", codec, 8)
    np.testing.assert_array_equal(scan.selections, shard.selections)
    np.testing.assert_allclose(np.asarray(scan.server_state.q),
                               np.asarray(shard.server_state.q),
                               rtol=1e-5, atol=1e-6)
    checked += 1

print(f"SHARDED_PARITY_OK checked={checked}")
"""


@pytest.mark.subprocess
@pytest.mark.parametrize("devices", [8])
def test_sharded_matches_scan_bitwise_all_strategies(devices):
    """All four strategies x {fp32, int8} at D=8 + the D=1 identity, in a
    subprocess seeded with fake CPU devices (one process, one jax init)."""
    env = fake_cpu_devices_env(devices)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _PARITY_SCRIPT],
        capture_output=True, text=True, env=env, cwd=str(REPO_ROOT),
        timeout=1200,
    )
    assert proc.returncode == 0, (
        f"parity subprocess failed\nstdout:\n{proc.stdout}\n"
        f"stderr:\n{proc.stderr}")
    assert "SHARDED_PARITY_OK" in proc.stdout
    assert "checked=12" in proc.stdout


# --------------------------------------------------------------------- #
# in-process properties (single device)
# --------------------------------------------------------------------- #
def test_shard_backend_single_device_matches_scan():
    from dataclasses import replace

    from repro.federated.simulation import FLSimConfig, run_fcf_simulation

    train, test = _mini_data()
    cfg = FLSimConfig(strategy="bts", keep_fraction=0.25, rounds=6, theta=10,
                      eval_every=3, eval_users=40, seed=0, codec="int8",
                      record_selections=True)
    scan = run_fcf_simulation(train, test, cfg)
    shard = run_fcf_simulation(
        train, test, replace(cfg, backend="shard", mesh_shards=1))
    np.testing.assert_array_equal(scan.selections, shard.selections)
    np.testing.assert_array_equal(np.asarray(scan.server_state.q),
                                  np.asarray(shard.server_state.q))
    assert scan.history.series("f1") == shard.history.series("f1")
    assert (scan.bytes_down, scan.bytes_up) == \
        (shard.bytes_down, shard.bytes_up)


def test_cohort_blocking_is_scan_python_consistent():
    """cohort_shards > 1 (padded blocks included) keeps scan == python."""
    from dataclasses import replace

    from repro.federated.simulation import FLSimConfig, run_fcf_simulation

    train, test = _mini_data()
    # theta=10 over 4 blocks -> blocks of 3 with 2 padded users
    cfg = FLSimConfig(strategy="bts", keep_fraction=0.25, rounds=6, theta=10,
                      eval_every=3, eval_users=40, seed=0, cohort_shards=4,
                      record_selections=True)
    scan = run_fcf_simulation(train, test, cfg)
    py = run_fcf_simulation(train, test, replace(cfg, backend="python"))
    np.testing.assert_array_equal(scan.selections, py.selections)
    np.testing.assert_array_equal(np.asarray(scan.server_state.q),
                                  np.asarray(py.server_state.q))


def test_cohort_blocking_stays_numerically_close_to_unblocked():
    """Blocking changes the gradient summation order (ulp-level), never the
    math: trajectories at C=1 and C=4 agree to float tolerance."""
    from dataclasses import replace

    from repro.federated.simulation import FLSimConfig, run_fcf_simulation

    train, test = _mini_data()
    cfg = FLSimConfig(strategy="random", keep_fraction=0.25, rounds=6,
                      theta=10, eval_every=3, eval_users=40, seed=0)
    r1 = run_fcf_simulation(train, test, cfg)
    r4 = run_fcf_simulation(train, test, replace(cfg, cohort_shards=4))
    np.testing.assert_allclose(np.asarray(r1.server_state.q),
                               np.asarray(r4.server_state.q),
                               rtol=1e-4, atol=1e-5)


def test_shard_backend_validates_divisibility_and_devices():
    from dataclasses import replace

    from repro.federated.simulation import FLSimConfig, run_fcf_simulation

    train, test = _mini_data()           # 80 items
    cfg = FLSimConfig(strategy="random", keep_fraction=0.25, rounds=2,
                      theta=10, eval_every=2, eval_users=20, seed=0,
                      backend="shard")
    # 3 does not divide 80 rows -> divisibility guard (checked before the
    # mesh is built, so it fires even on a single-device host)
    with pytest.raises(ValueError, match="divide evenly"):
        run_fcf_simulation(train, test, replace(cfg, mesh_shards=3))
    # 16 divides 80, but this host has no 16-device mesh
    with pytest.raises(ValueError, match="devices"):
        run_fcf_simulation(train, test, replace(cfg, mesh_shards=16))
    with pytest.raises(ValueError, match="unknown|backend|one of"):
        run_fcf_simulation(train, test, replace(cfg, backend="bogus"))


def test_fcf_state_pspecs_shards_only_row_tables():
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.cf.server import server_init
    from repro.compress import CodecConfig
    from repro.core.selector import SelectorConfig
    from repro.launch.sharding import fcf_state_pspecs

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (40, 8))
    sel_cfg = SelectorConfig(strategy="bts", num_arms=40, num_select=10, dim=8)
    state = server_init(q, sel_cfg, key=key,
                        codec_cfg=CodecConfig(name="topk"))
    specs = fcf_state_pspecs(state)
    assert specs.q == P("data", None)
    assert specs.opt.m == P("data", None)
    assert specs.opt.v == P("data", None)
    assert specs.opt.t == P()                    # (M,) vector: replicated
    assert specs.sel.reward.v == P("data", None)
    assert specs.sel.reward.prev_grad == P("data", None)
    assert specs.sel.bts.counts == P()           # (M,) posterior: replicated
    assert specs.codec == P("data", None)        # topk EF residual
    assert specs.key == P() and specs.t == P()


def test_fake_cpu_devices_env_replaces_previous_flag():
    env = fake_cpu_devices_env(4, env={"XLA_FLAGS": (
        "--xla_foo=1 --xla_force_host_platform_device_count=2")})
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert env["JAX_PLATFORMS"] == "cpu"     # the child never takes a chip
    assert "device_count=2" not in env["XLA_FLAGS"]
    assert "--xla_foo=1" in env["XLA_FLAGS"]


def test_roofline_peaks_come_from_device_kind():
    from repro.launch.hlo_analysis import peaks_for, roofline_terms

    peaks = peaks_for("TPU v5 lite")
    assert peaks["peak_flops"] == 197e12 and peaks["hbm_bw"] == 819e9
    terms = roofline_terms(197e12, 2 * 819e9, 0.0, 1, **peaks)
    assert terms["bottleneck"] == "memory" and terms["step_time_s"] == 2.0
    with pytest.raises(ValueError, match="'cpu'"):
        peaks_for("cpu")                      # unknown device: an error
    with pytest.raises(TypeError):
        roofline_terms(1.0, 1.0, 0.0, 1)      # no silent default peak
