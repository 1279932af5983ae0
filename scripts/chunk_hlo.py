"""Hash the scan engine's chunk program for a benchmark configuration.

Runs ``run_fcf_simulation`` on the CPU at the configuration's real size up
to its first chunk, lowers that ``scan_chunk`` program instead of running
it, drops the debug metadata (source lines, scope names) from its HLO text
and prints the text's length and sha256. Run it in two checkouts to show
that a change leaves a cell's chunk program as it was:

    PYTHONPATH=<checkout>/src:<checkout> JAX_PLATFORMS=cpu \\
        python <checkout>/scripts/chunk_hlo.py fcf-lastfm [--mix train.bts]

The data comes from the benchmark's generator, cached under
``bench/.cache/data`` of the checkout that holds ``bench/``.
"""
import argparse
import hashlib
import json
import re

import jax
import jax.numpy as jnp


class _Lowered(Exception):
    pass


def chunk_hlo(config: str, mix: str) -> str:
    from bench.harness import data, train
    from bench.harness.device import CACHE_DIR, ROOT
    from repro.federated import run_fcf_simulation

    cfg = json.loads((ROOT / "bench/configs" / f"{config}.json").read_text())
    mix_ = json.loads((ROOT / "bench/traffic" / f"{mix}.json").read_text())
    tr, te = data.dataset(cfg["data"], CACHE_DIR / "data")
    if data.layout(cfg["data"]) == "dense":
        tr, te = jnp.asarray(tr, jnp.float32), jnp.asarray(te, jnp.float32)
    real, got = jax.jit, {}

    def jit(fn, *a, **k):
        jitted = real(fn, *a, **k)
        if getattr(fn, "__name__", "") != "scan_chunk":
            return jitted

        def lower(*args):
            got["hlo"] = jitted.lower(*args).as_text(dialect="hlo")
            raise _Lowered
        return lower

    jax.jit = jit
    try:
        run_fcf_simulation(tr, te, train.sim_config(cfg, mix_, 1234, None))
    except _Lowered:
        pass
    finally:
        jax.jit = real
    text = re.sub(r", metadata=\{[^}]*\}", "", got["hlo"])
    return re.sub(r"\n\s*\n", "\n", text)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config")
    ap.add_argument("--mix", default="train.bts")
    a = ap.parse_args(argv)
    text = chunk_hlo(a.config, a.mix)
    print(a.config, a.mix, len(text),
          hashlib.sha256(text.encode()).hexdigest())


if __name__ == "__main__":
    main()
