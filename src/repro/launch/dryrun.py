import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape x mesh) combination this lowers the
appropriate step (train_step / prefill_step / decode_step) with explicit
in/out shardings on the production mesh, compiles it, and records

  * memory_analysis()  -- proves the per-device working set fits,
  * cost_analysis()    -- HLO FLOPs / bytes for the roofline,
  * collective traffic -- parsed from the compiled HLO (hlo_analysis).

The two XLA_FLAGS lines above MUST stay the first statements in the file:
jax locks the device count on first init, and only the dry-run may see 512
placeholder devices (tests/benches see the single real CPU device).

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  python -m repro.launch.dryrun --all                    # 10x4 single-pod
  python -m repro.launch.dryrun --all --multi-pod        # 2x16x16 sweep
"""
import argparse
import dataclasses
import json
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.configs.registry import get_config, list_archs
from repro.configs.shapes import INPUT_SHAPES, InputShape
from repro.launch.hlo_analysis import (
    collective_bytes, peaks_for, roofline_terms,
)
from repro.launch.mesh import batch_axes, data_axis_size, make_production_mesh
from repro.launch.sharding import input_pspecs, param_pspecs, to_shardings
from repro.models import lm

_KEY_SPEC = jax.ShapeDtypeStruct((2,), jnp.uint32)
# the chip the production mesh is made of (launch/mesh.py: v5e pod slices);
# the dry-run's placeholder devices are CPUs, so the target is named here
TARGET_DEVICE_KIND = "TPU v5 lite"


def skip_reason(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    """DESIGN.md section 4 skip rules (documented, not silent)."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return ("full-attention blocks are quadratic at 524k context; "
                "long_500k is assigned only to sub-quadratic archs")
    return None


# --------------------------------------------------------------------- #
# step builders: (fn, arg_shapes, in_specs, out_specs)
# --------------------------------------------------------------------- #
def _kv_model_shard(shape: InputShape) -> bool:
    return (os.environ.get("REPRO_KV_MODEL_SHARD", "0") == "1"
            and shape.kind == "decode")


def build_lowerable(cfg: ModelConfig, shape: InputShape, mesh) -> Tuple:
    baxes = batch_axes(mesh)
    kv_ms = _kv_model_shard(shape)
    seq_shard = (not kv_ms and shape.kind == "decode"
                 and shape.global_batch % data_axis_size(mesh) != 0)
    specs = lm.input_specs(cfg, shape)
    in_batch_specs = input_pspecs(cfg, specs, mesh, seq_shard=seq_shard,
                                  kv_model_shard=kv_ms)

    if shape.kind == "train":
        state_shape = jax.eval_shape(
            lambda k: lm.init_train_state(cfg, k), _KEY_SPEC)
        m_specs = param_pspecs(cfg, state_shape.m)
        if os.environ.get("REPRO_ZERO", "0") == "1":
            from repro.launch.sharding import zero_shard_moments
            m_specs = zero_shard_moments(cfg, m_specs, state_shape.m)
        state_specs = lm.TrainState(
            params=param_pspecs(cfg, state_shape.params),
            m=m_specs, v=m_specs,
            step=P())

        def step(state, batch):
            return lm.train_step(state, batch, cfg)

        return (step, (state_shape, specs),
                (state_specs, in_batch_specs), (state_specs, P()))

    params_shape = jax.eval_shape(
        lambda k: lm.init_lm_params(cfg, k), _KEY_SPEC)
    pspecs = param_pspecs(cfg, params_shape)
    logit_spec = P(None if seq_shard else baxes, "model")

    if shape.kind == "prefill":
        def step(params, inputs):
            return lm.prefill_step(
                params, cfg, inputs["tokens"],
                prefix_embeds=inputs.get("prefix_embeds"),
                enc_embeds=inputs.get("enc_embeds"))

        out_shape = jax.eval_shape(step, params_shape, specs)
        cache_specs = input_pspecs(cfg, out_shape[1], mesh,
                                   seq_shard=seq_shard)
        return (step, (params_shape, specs),
                (pspecs, in_batch_specs), (logit_spec, cache_specs))

    if shape.kind == "decode":
        def step(params, inputs):
            return lm.decode_step(
                params, cfg, inputs["cache"], inputs["token"], inputs["pos"],
                enc_out=inputs.get("enc_out"))

        out_shape = jax.eval_shape(step, params_shape, specs)
        cache_specs = input_pspecs(cfg, out_shape[1], mesh,
                                   seq_shard=seq_shard, kv_model_shard=kv_ms)
        return (step, (params_shape, specs),
                (pspecs, in_batch_specs), (logit_spec, cache_specs))

    raise ValueError(shape.kind)


def payload_builder(keep_fraction: float = 0.10, shard_rows: bool = True):
    """Builder for the paper-technique train step: vocab-table gradients
    restricted to the bandit-selected 10% of rows (lm.payload_train_step).
    ``shard_rows`` shards the (M_s, d) row block over the model axis —
    the §Perf lever that makes the row collective 16x smaller."""
    def build(cfg: ModelConfig, shape: InputShape, mesh):
        assert shape.kind == "train", "payload step applies to training"
        baxes = batch_axes(mesh)
        specs = lm.input_specs(cfg, shape)
        in_batch_specs = input_pspecs(cfg, specs, mesh)
        state_shape = jax.eval_shape(
            lambda k: lm.init_train_state(cfg, k), _KEY_SPEC)
        state_specs = lm.TrainState(
            params=param_pspecs(cfg, state_shape.params),
            m=param_pspecs(cfg, state_shape.m),
            v=param_pspecs(cfg, state_shape.v),
            step=P())
        m_s = max(16, int(keep_fraction * cfg.padded_vocab) // 16 * 16)
        sel = jax.ShapeDtypeStruct((m_s,), jnp.int32)
        row_spec = P("model", None) if shard_rows else P(None, None)

        def step(state, batch, selected):
            return lm.payload_train_step(state, batch, selected, cfg,
                                         row_spec=row_spec)

        return (step, (state_shape, specs, sel),
                (state_specs, in_batch_specs, P()),
                (state_specs, P(), row_spec))
    return build


# --------------------------------------------------------------------- #
# while-body cost correction
#
# XLA's HloCostAnalysis visits each while body ONCE — it does not multiply
# by trip count — so a scanned P-period model under-reports everything that
# lives inside the layer loop by ~P×. We correct exactly with two shallow
# UNROLLED probes of the same config: U1 (1 period) and U2 (2 periods) give
# per-period cost B = U2 − U1 and loop-free overhead O = U1 − B; the
# corrected full-model cost is  S_full + (P − 1)·B  (S_full already counts
# the body once plus all out-of-loop work including remainder layers).
# Valid because every while in our programs is a layer scan with the same
# trip count P (encoder and decoder periods are equal for the enc-dec arch).
# --------------------------------------------------------------------- #
def _lower_compile(cfg, shape, mesh, builder):
    from repro.utils import hints
    step, arg_shapes, in_specs, out_specs = builder(cfg, shape, mesh)
    with mesh, hints.batch_axes(batch_axes(mesh), mesh=mesh,
                                kv_time_shard=_kv_model_shard(shape)):
        jitted = jax.jit(step,
                         in_shardings=to_shardings(mesh, in_specs),
                         out_shardings=to_shardings(mesh, out_specs))
        lowered = jitted.lower(*arg_shapes)
        compiled = lowered.compile()
    return compiled


def _probe_cfg(cfg: ModelConfig, n_periods: int) -> ModelConfig:
    reps = {"num_layers": n_periods * len(cfg.block_pattern)}
    if cfg.is_enc_dec:
        reps["encoder_layers"] = n_periods
    return dataclasses.replace(cfg, **reps)


def _extract_costs(compiled) -> Dict[str, float]:
    cost = _cost_dict(compiled)
    coll = collective_bytes(compiled.as_text())
    return {"flops": cost.get("flops", 0.0),
            "bytes": cost.get("bytes accessed", 0.0),
            "coll": float(coll["total"])}


def corrected_costs(cfg: ModelConfig, shape: InputShape, mesh,
                    builder, scanned: Dict[str, float]) -> Dict[str, float]:
    """Trip-count-corrected {flops, bytes} for the full model. Collective
    bytes are NOT probe-corrected — they use the structured while-body
    accounting in hlo_analysis (probes would double-count the once-per-step
    stacked gradient sync, which unrolled probes emit per layer)."""
    periods = cfg.num_layers // len(cfg.block_pattern)
    out = dict(scanned)
    if periods <= 1:
        return out
    os.environ["REPRO_SCAN_UNROLL"] = "1"
    try:
        u1 = _extract_costs(_lower_compile(_probe_cfg(cfg, 1), shape, mesh,
                                           builder))
        u2 = _extract_costs(_lower_compile(_probe_cfg(cfg, 2), shape, mesh,
                                           builder))
    finally:
        os.environ["REPRO_SCAN_UNROLL"] = "0"
    for k in ("flops", "bytes"):
        body = max(u2[k] - u1[k], 0.0)
        out[k] = scanned[k] + (periods - 1) * body
        out[f"probe_body_{k}"] = body
    return out


def _cost_dict(compiled) -> Dict[str, float]:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float))}


def _memory_dict(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        if hasattr(ma, attr):
            out[attr] = float(getattr(ma, attr))
    return out


def run_pair(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: Optional[str] = None,
             step_override=None, tag: str = "") -> Dict[str, Any]:
    """Lower + compile one (arch, shape, mesh); return the roofline record."""
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_tag, "kind": shape.kind}

    reason = skip_reason(cfg, shape)
    if reason:
        rec["status"] = "skipped"
        rec["skip_reason"] = reason
        _save(rec, out_dir, tag)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    num_chips = mesh.size
    builder = step_override or build_lowerable
    step, arg_shapes, in_specs, out_specs = builder(cfg, shape, mesh)

    from repro.utils import hints
    t0 = time.time()
    with mesh, hints.batch_axes(batch_axes(mesh), mesh=mesh,
                                kv_time_shard=_kv_model_shard(shape)):
        jitted = jax.jit(
            step,
            in_shardings=to_shardings(mesh, in_specs),
            out_shardings=to_shardings(mesh, out_specs))
        lowered = jitted.lower(*arg_shapes)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    cost = _cost_dict(compiled)
    memory = _memory_dict(compiled)
    periods = cfg.num_layers // len(cfg.block_pattern)
    coll = collective_bytes(compiled.as_text(), while_trip=periods)

    scanned = {"flops": cost.get("flops", 0.0),
               "bytes": cost.get("bytes accessed", 0.0),
               "coll": float(coll["total"])}
    corrected = corrected_costs(cfg, shape, mesh, builder, scanned)
    terms = roofline_terms(corrected["flops"], corrected["bytes"],
                           corrected["coll"], num_chips,
                           **peaks_for(TARGET_DEVICE_KIND))

    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    tokens = shape.tokens
    model_flops = 6.0 * n_active * tokens if shape.kind == "train" else (
        2.0 * n_active * tokens if shape.kind == "prefill"
        else 2.0 * n_active * shape.global_batch)
    rec.update({
        "status": "ok",
        "num_chips": num_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "cost_analysis": cost,
        "memory_analysis": memory,
        "collectives": coll,
        "scanned_costs": scanned,
        "corrected_costs": corrected,
        "roofline": terms,
        "params": n_params,
        "active_params": n_active,
        "model_flops_global": model_flops,
        "model_flops_per_chip": model_flops / num_chips,
        "useful_flops_ratio": (model_flops / num_chips) / corrected["flops"]
        if corrected["flops"] else None,
    })
    _save(rec, out_dir, tag)
    return rec


def _save(rec: Dict, out_dir: Optional[str], tag: str = "") -> None:
    if not out_dir:
        return
    d = os.path.join(out_dir, rec["mesh"])
    os.makedirs(d, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(d, f"{rec['arch']}__{rec['shape']}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)


def _fmt(rec: Dict) -> str:
    if rec["status"] != "ok":
        return (f"{rec['arch']:<24} {rec['shape']:<12} {rec['mesh']:<11} "
                f"SKIP ({rec['skip_reason'][:60]}...)")
    r = rec["roofline"]
    return (f"{rec['arch']:<24} {rec['shape']:<12} {rec['mesh']:<11} "
            f"compile={rec['compile_s']:>6.1f}s "
            f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
            f"coll={r['collective_s']:.3e}s -> {r['bottleneck']}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch x shape)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out-dir", default="results/dryrun")
    ap.add_argument("--verbose", action="store_true",
                    help="print full memory/cost analysis per pair")
    ap.add_argument("--payload", action="store_true",
                    help="lower the payload-selected train step (10%% rows)")
    ap.add_argument("--payload-replicated-rows", action="store_true",
                    help="ablation: keep the selected-row block replicated")
    args = ap.parse_args()

    if args.all:
        pairs = [(a, s) for a in list_archs() for s in INPUT_SHAPES]
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        pairs = [(args.arch, args.shape)]

    override, tag = None, ""
    if args.payload:
        override = payload_builder(
            shard_rows=not args.payload_replicated_rows)
        tag = ("payload_repl" if args.payload_replicated_rows else "payload")

    failures = []
    for arch, shape in pairs:
        try:
            rec = run_pair(arch, shape, multi_pod=args.multi_pod,
                           out_dir=args.out_dir,
                           step_override=override, tag=tag)
        except Exception as e:
            traceback.print_exc()
            failures.append((arch, shape, repr(e)))
            print(f"{arch:<24} {shape:<12} FAILED: {e}")
            continue
        print(_fmt(rec), flush=True)
        if args.verbose and rec["status"] == "ok":
            print("  memory_analysis:", rec["memory_analysis"])
            print("  cost_analysis:",
                  {k: v for k, v in rec["cost_analysis"].items()
                   if k in ("flops", "bytes accessed")})
            print("  collectives:", rec["collectives"])

    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nALL PAIRS LOWERED + COMPILED OK")


if __name__ == "__main__":
    main()
