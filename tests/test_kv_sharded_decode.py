"""Correctness of the §Perf distributed-LSE decode path: the KV-time-
sharded attention (shard_map over a 16-device mesh) must produce the same
logits as the plain single-device decode.

Runs in a subprocess of 16 fake CPU devices (``fake_cpu_devices_env``)
because jax pins the device count at first init (the main pytest process
must keep seeing 1 device).
"""
import subprocess
import sys

import pytest

from repro.launch.mesh import fake_cpu_devices_env

_SCRIPT = r"""
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs.registry import get_config
from repro.launch.sharding import input_pspecs, param_pspecs, to_shardings
from repro.models import lm
from repro.utils import hints

cfg = get_config("qwen3-4b").reduced(num_layers=2, d_model=256, vocab=1024)
key = jax.random.PRNGKey(0)
params = lm.init_lm_params(cfg, key)
B, T = 4, 64
cache = lm.init_decode_cache(cfg, B, T)

# prefill a few tokens the plain way so the cache is non-trivial
tok0 = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0, cfg.vocab_size, jnp.int32)
tok1 = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0, cfg.vocab_size, jnp.int32)
logits_a, cache_a = lm.decode_step(params, cfg, cache, tok0, jnp.asarray(0, jnp.int32))
ref_logits, _ = lm.decode_step(params, cfg, cache_a, tok1, jnp.asarray(1, jnp.int32))

mesh = jax.make_mesh((2, 8), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
with mesh, hints.batch_axes(("data",), mesh=mesh, kv_time_shard=True):
    step = jax.jit(lambda p, c, t, pos: lm.decode_step(p, cfg, c, t, pos))
    logits_b, cache_b = step(params, cache, tok0, jnp.asarray(0, jnp.int32))
    sh_logits, _ = step(params, cache_b, tok1, jnp.asarray(1, jnp.int32))

np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(sh_logits),
                           rtol=2e-4, atol=2e-4)
print("KV-SHARDED-DECODE-OK")
"""


@pytest.mark.slow
def test_kv_sharded_decode_matches_reference():
    env = fake_cpu_devices_env(16)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "KV-SHARDED-DECODE-OK" in out.stdout, (
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}")
