"""Compressed Adam moment storage — payload optimization for the OPTIMIZER.

The paper shrinks what crosses the wire; this module shrinks what stays
resident. At M=10^7 items the fp32 Adam moments are 2x the size of the
model itself (8 bytes/value vs Q's 4), so the largest table one host can
train is bounded by optimizer state, not the model. The same per-row-scale
quantization the wire codecs use (:mod:`repro.compress.codecs` — encode
and decode stay property-tested in ONE place) applies to the moments:

  * ``bf16``     — 2 bytes/value, round-to-nearest-even cast. 0.5x fp32.
  * ``int8``     — 1 byte/value + one float32 scale per row
    (:class:`QuantMoment`), written with STOCHASTIC rounding so sub-quantum
    updates accumulate in expectation instead of rounding away. 0.26x fp32
    at K=16.
  * ``factored`` — SM3/Adafactor-style factored SECOND moment: the (M, K)
    accumulator collapses to a per-row (M,) + per-column (K,) pair
    (:class:`FactoredMoment`) with ``v[i, j]`` estimated as
    ``r[i] * c[j] / mean(c)``. O(M+K) instead of O(M*K) — the second
    moment all but vanishes from the resident budget.

:class:`MomentCodecConfig` is static configuration (a hashable NamedTuple
living in ``FCFServerConfig``, never in the scan carry); the moment
*representation* it selects is an ordinary pytree riding ``AdamState.m`` /
``AdamState.v``, so compressed states scan, vmap, shard (codes and scales
are rank-2 leading-M leaves — ``fcf_state_pspecs`` row-shards them like
every other table) and checkpoint (flat-key npz) with zero special cases.

FROZEN CONTRACT: the default config (``m_dtype="fp32", v_dtype="fp32"``,
or a ``None`` moment config anywhere one is accepted) is *not routed
through this module at all* — :func:`repro.optim.adam.adam_init` and
``adam_update_rows_scattered`` take their historical code paths and
compile byte-identical programs, keeping every existing trajectory
bit-for-bit across the scan/python/shard/async backends.

Update semantics (:func:`adam_update_rows_compressed`): decode the
selected rows' moments to float32, run EXACTLY the dense-path Adam math on
those (M_s, K) tiles, re-encode, scatter. The fp32 moments of the full
table are never materialized — only payload-sized tiles move — and on the
single-device hot path the decode-gather and requant-scatter are fused
Pallas kernels (:mod:`repro.kernels.moment_quant`), one HBM trip per row.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.compress.codecs import (
    dequantize_rows, quantize_rows, quantize_rows_stochastic,
)
from repro.optim.adam import AdamConfig, AdamState

M_DTYPES = ("fp32", "bf16", "int8")
V_DTYPES = ("fp32", "bf16", "int8", "factored")

# fold_in salts deriving the two independent stochastic-rounding streams
# from one per-round key (m and v must not share dither)
_SALT_M = 0x6d
_SALT_V = 0x76


class MomentCodecConfig(NamedTuple):
    """Static (hashable) moment-storage config, fixed for a whole run."""

    m_dtype: str = "fp32"            # fp32 | bf16 | int8
    v_dtype: str = "fp32"            # fp32 | bf16 | int8 | factored
    # int8 write path: stochastic rounding (floor(x/scale + u), u~U[0,1))
    # keeps the quantized moment an unbiased estimate of the fp32 one.
    # Irrelevant for fp32/bf16/factored.
    stochastic_rounding: bool = True


class QuantMoment(NamedTuple):
    """int8 moment table: per-row-scale codes, the wire codec's layout."""

    codes: jax.Array                 # (M, K) int8
    scales: jax.Array                # (M, 1) float32


class FactoredMoment(NamedTuple):
    """SM3-style factored second moment: (M, K) collapsed to (M,) + (K,).

    ``row[i]`` and ``col[j]`` are EMAs of the per-row / per-column mean
    squared gradient over the rows each commit touches; the full second
    moment is estimated as ``row[i] * col[j] / mean(col)`` (exact for
    rank-1 squared gradients, and exactly ``row`` when K == 1). ``row``
    uses the per-row timesteps for bias correction (rows commit at
    different frequencies under bandit selection); ``col`` aggregates
    over every commit and carries its own scalar timestep.
    """

    row: jax.Array                   # (M,) float32
    col: jax.Array                   # (K,) float32
    col_t: jax.Array                 # () int32 — commits observed


def validate_config(cfg: MomentCodecConfig) -> None:
    if cfg.m_dtype not in M_DTYPES:
        raise ValueError(
            f"moment m_dtype must be one of {M_DTYPES}, got {cfg.m_dtype!r}")
    if cfg.v_dtype not in V_DTYPES:
        raise ValueError(
            f"moment v_dtype must be one of {V_DTYPES}, got {cfg.v_dtype!r}")


def is_compressed(cfg: Optional[MomentCodecConfig]) -> bool:
    """True when ``cfg`` selects anything other than the frozen fp32 path."""
    if cfg is None:
        return False
    validate_config(cfg)
    return cfg.m_dtype != "fp32" or cfg.v_dtype != "fp32"


def needs_sr_key(cfg: Optional[MomentCodecConfig]) -> bool:
    """True when the update needs a PRNG key (stochastic int8 writes)."""
    return (is_compressed(cfg) and cfg.stochastic_rounding
            and "int8" in (cfg.m_dtype, cfg.v_dtype))


def moment_init(dtype: str, num_rows: int, dim: int) -> Any:
    """All-zero moment pytree for one (num_rows, dim) table."""
    if dtype == "fp32":
        return jnp.zeros((num_rows, dim), jnp.float32)
    if dtype == "bf16":
        return jnp.zeros((num_rows, dim), jnp.bfloat16)
    if dtype == "int8":
        return QuantMoment(codes=jnp.zeros((num_rows, dim), jnp.int8),
                           scales=jnp.zeros((num_rows, 1), jnp.float32))
    if dtype == "factored":
        return FactoredMoment(row=jnp.zeros((num_rows,), jnp.float32),
                              col=jnp.zeros((dim,), jnp.float32),
                              col_t=jnp.zeros((), jnp.int32))
    raise ValueError(f"unknown moment dtype {dtype!r}")


def moment_nbytes(dtype: str, num_rows: int, dim: int) -> int:
    """Resident bytes of one moment table (static accounting)."""
    if dtype == "fp32":
        return num_rows * dim * 4
    if dtype == "bf16":
        return num_rows * dim * 2
    if dtype == "int8":
        return num_rows * dim + num_rows * 4
    if dtype == "factored":
        return num_rows * 4 + dim * 4 + 4
    raise ValueError(f"unknown moment dtype {dtype!r}")


def state_nbytes(cfg: Optional[MomentCodecConfig], num_rows: int,
                 dim: int) -> int:
    """Resident bytes of a full per-row AdamState (m + v + (M,) timesteps)."""
    c = cfg or MomentCodecConfig()
    return (moment_nbytes(c.m_dtype, num_rows, dim)
            + moment_nbytes(c.v_dtype, num_rows, dim)
            + num_rows * 4)


# ===================================================================== #
# row-tile encode / decode — all math delegated to compress.codecs
# ===================================================================== #
def decode_moment_rows(dtype: str, mom: Any, indices: jax.Array,
                       row_ops, fused: bool,
                       need_raw: bool = False) -> Tuple[jax.Array, Any]:
    """Gather + decode the selected rows of a dense moment table.

    Returns ``(rows_f32, raw_rows)``: the float32 (M_s, K) tile the Adam
    math runs on, plus (when ``need_raw`` — the fault-mask path) the
    gathered rows in their STORED representation — what a masked
    (fault-rejected) row must scatter back for an exact no-op, since a
    stochastic re-encode of a decoded row is not the identity. ``fused``
    (single-device resident tables only) routes the int8 path through the
    fused gather+dequant kernel; the sharded path composes the per-leaf
    collective gathers and dequantizes the assembled tiles — per-row
    encoding makes the two bit-identical.
    """
    from repro.kernels import ops
    from jax.lax import optimization_barrier

    if dtype == "bf16":
        raw = row_ops.gather(mom, indices)
        return raw.astype(jnp.float32), raw
    if dtype == "int8":
        if fused and not need_raw:
            rows = optimization_barrier(
                ops.gather_dequant_rows(mom.codes, mom.scales, indices))
            return rows, None
        code_rows = row_ops.gather(mom.codes, indices)
        scale_rows = row_ops.gather(mom.scales, indices)
        return (dequantize_rows(code_rows, scale_rows),
                QuantMoment(codes=code_rows, scales=scale_rows))
    raise ValueError(f"no dense row decode for moment dtype {dtype!r}")


def encode_scatter_moment_rows(
    dtype: str, mom: Any, indices: jax.Array, rows_f32: jax.Array,
    raw_old: Any, row_mask: Optional[jax.Array],
    noise: Optional[jax.Array], row_ops, fused: bool,
) -> Any:
    """Re-encode updated float32 row tiles and scatter them back.

    ``noise`` (U[0,1), same shape as ``rows_f32``) selects stochastic
    rounding on the int8 path; ``None`` is round-to-nearest. ``row_mask``
    restores the ORIGINAL stored rows (``raw_old``) for False entries —
    bit-exact no-ops, the fault layer's reject contract.
    """
    from repro.kernels import ops

    if dtype == "bf16":
        out = rows_f32.astype(jnp.bfloat16)
        if row_mask is not None:
            out = jnp.where(row_mask[:, None], out, raw_old)
        return row_ops.scatter_set(mom, indices, out)
    if dtype == "int8":
        if fused and row_mask is None:
            codes, scales = ops.quant_scatter_set_rows(
                mom.codes, mom.scales, indices, rows_f32, noise)
            return QuantMoment(codes=codes, scales=scales)
        if noise is not None:
            code_rows, scale_rows = quantize_rows_stochastic(rows_f32, noise)
        else:
            code_rows, scale_rows = quantize_rows(rows_f32, nbits=8)
        if row_mask is not None:
            keep = row_mask[:, None]
            code_rows = jnp.where(keep, code_rows, raw_old.codes)
            scale_rows = jnp.where(keep, scale_rows, raw_old.scales)
        return QuantMoment(
            codes=row_ops.scatter_set(mom.codes, indices, code_rows),
            scales=row_ops.scatter_set(mom.scales, indices, scale_rows))
    raise ValueError(f"no dense row encode for moment dtype {dtype!r}")


# ===================================================================== #
# the compressed sparse-Adam commit
# ===================================================================== #
def adam_update_rows_compressed(
    grad_rows: jax.Array,   # (M_s, K) aggregated gradient for selected rows
    indices: jax.Array,     # (M_s,) row ids
    state: AdamState,       # moments stored per ``moment``'s dtypes
    table: jax.Array,       # (M, K) full parameter table
    config: AdamConfig,
    moment: MomentCodecConfig,
    *,
    key: Optional[jax.Array] = None,     # per-commit PRNG key (SR dither)
    row_ops=None,
    row_weights: Optional[jax.Array] = None,
    row_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, AdamState]:
    """:func:`repro.optim.adam.adam_update_rows_scattered` over compressed
    moment storage: decode the selected tiles, run the IDENTICAL fp32 Adam
    math, re-encode, scatter. Entered only for genuinely compressed
    configs — the fp32 default never reaches this function (frozen
    contract). ``key`` is required when the config stochastically rounds
    an int8 moment; two independent dither streams are folded out of it.

    The factored second moment updates its (M,) row EMA on the selected
    rows (per-row timestep bias correction, like every dense moment) and
    its (K,) column EMA once per commit from the column mean of g^2 over
    the committed rows (masked rows excluded); ``v_hat`` is the SM3-style
    outer-product estimate ``r_hat[i] * c_hat[j] / mean(c_hat)``.
    """
    from repro.kernels import ops as kops
    from jax.lax import optimization_barrier

    validate_config(moment)
    if needs_sr_key(moment) and key is None:
        raise ValueError(
            "MomentCodecConfig with stochastic_rounding=True and an int8 "
            "moment needs a per-commit PRNG key (pass key=...)")
    fused = row_ops is None
    if row_ops is None:
        row_ops = kops.default_row_ops()
    b1, b2 = config.beta1, config.beta2
    t_rows = state.t[indices] + 1            # (M_s,)
    tf = t_rows.astype(jnp.float32)[:, None]

    noise_m = noise_v = None
    if moment.stochastic_rounding and key is not None:
        if moment.m_dtype == "int8":
            noise_m = jax.random.uniform(
                jax.random.fold_in(key, _SALT_M), grad_rows.shape)
        if moment.v_dtype == "int8":
            noise_v = jax.random.uniform(
                jax.random.fold_in(key, _SALT_V), grad_rows.shape)

    # first moment: decode -> EMA -> bias-correct (dense-path math verbatim)
    if moment.m_dtype == "fp32":
        m_old, m_raw = row_ops.gather(state.m, indices), None
    else:
        m_old, m_raw = decode_moment_rows(
            moment.m_dtype, state.m, indices, row_ops, fused,
            need_raw=row_mask is not None)
    m_rows = b1 * m_old + (1 - b1) * grad_rows
    mhat = m_rows / (1.0 - jnp.power(b1, tf))

    # second moment: dense (any dtype) or factored estimate
    g2 = jnp.square(grad_rows)
    factored = moment.v_dtype == "factored"
    if factored:
        fac: FactoredMoment = state.v
        r_old = fac.row[indices]                               # (M_s,)
        r_rows = b2 * r_old + (1 - b2) * jnp.mean(g2, axis=1)
        if row_mask is not None:
            w = row_mask.astype(jnp.float32)[:, None]
            col_obs = (jnp.sum(g2 * w, axis=0)
                       / jnp.maximum(jnp.sum(w), 1.0))
        else:
            col_obs = jnp.mean(g2, axis=0)                     # (K,)
        col_t = fac.col_t + 1
        c_new = b2 * fac.col + (1 - b2) * col_obs
        rhat = r_rows / (1.0 - jnp.power(b2, tf[:, 0]))        # (M_s,)
        chat = c_new / (1.0 - jnp.power(b2, col_t.astype(jnp.float32)))
        vhat = (rhat[:, None] * chat[None, :]
                / jnp.maximum(jnp.mean(chat), config.eps))
        v_rows = v_raw = None
    else:
        if moment.v_dtype == "fp32":
            v_old, v_raw = row_ops.gather(state.v, indices), None
        else:
            v_old, v_raw = decode_moment_rows(
                moment.v_dtype, state.v, indices, row_ops, fused,
                need_raw=row_mask is not None)
        v_rows = b2 * v_old + (1 - b2) * g2
        vhat = v_rows / (1.0 - jnp.power(b2, tf))

    step = config.lr * mhat / (jnp.sqrt(vhat) + config.eps)
    if row_weights is not None:
        step = step * row_weights.astype(jnp.float32)[:, None]
    table_old = row_ops.gather(table, indices)
    new_rows = table_old - step
    if row_mask is not None:
        keep = row_mask[:, None]
        new_rows = jnp.where(keep, new_rows, table_old)
        t_rows = jnp.where(row_mask, t_rows, state.t[indices])
        if factored:
            r_rows = jnp.where(row_mask, r_rows, r_old)
        if moment.m_dtype == "fp32":
            m_rows = jnp.where(keep, m_rows, m_old)
        if not factored and moment.v_dtype == "fp32":
            v_rows = jnp.where(keep, v_rows, v_old)
    # same fusion-boundary discipline as the fp32 path: pin the update
    # tiles' producer graphs before any scatter flavor consumes them
    barrier_v = r_rows if factored else v_rows
    m_rows, barrier_v, new_rows = optimization_barrier(
        (m_rows, barrier_v, new_rows))

    if moment.m_dtype == "fp32":
        new_m = row_ops.scatter_set(state.m, indices, m_rows)
    else:
        new_m = encode_scatter_moment_rows(
            moment.m_dtype, state.m, indices, m_rows, m_raw, row_mask,
            noise_m, row_ops, fused)
    if factored:
        new_v = FactoredMoment(
            row=state.v.row.at[indices].set(barrier_v),
            col=c_new, col_t=col_t)
    elif moment.v_dtype == "fp32":
        new_v = row_ops.scatter_set(state.v, indices, barrier_v)
    else:
        new_v = encode_scatter_moment_rows(
            moment.v_dtype, state.v, indices, barrier_v, v_raw, row_mask,
            noise_v, row_ops, fused)

    return (
        row_ops.scatter_set(table, indices, new_rows),
        AdamState(m=new_m, v=new_v, t=state.t.at[indices].set(t_rows)),
    )
