"""Fused compressed-moment Pallas kernels (repro.optim.state_compress).

With int8 Adam moments the sparse commit's per-row hot path becomes

  * read:  m_f32[i] = codes[idx[i]] * scales[idx[i]]   — gather the stored
    int8 row AND dequantize it, fused so each selected moment row makes a
    single HBM->VMEM trip and lands in VMEM already as the fp32 tile the
    Adam math consumes (:func:`gather_dequant_rows`);
  * write: (codes[idx[i]], scales[idx[i]]) = quantize(m_f32'[i]) — requant
    the updated fp32 tile and scatter it back into the resident int8
    table + scale vector in one kernel, both aliased in place
    (:func:`quant_scatter_set_rows`). The stochastic variant adds a U[0,1)
    dither operand and rounds with ``floor(x/scale + u)``.

The fp32 moments of the full (M, K) table are never materialized — the
whole point of compressed state. Same structure as
:mod:`repro.kernels.payload_quant`: one grid step per selected row,
scalar-prefetched indices steering the row DMA, (1, 1, K) blocks over the
(M, 1, K) row view.

BIT-EXACTNESS CONTRACT: the quantization math must reproduce
:func:`repro.compress.codecs.quantize_rows` /
``quantize_rows_stochastic`` / ``dequantize_rows`` bit-for-bit (same op
sequence), so a kernel-routed compressed update and the pure-codec
composed path (the sharded engine's per-leaf collective gathers) produce
identical trajectories. ``kernels/ref.py`` delegates to the codec
functions and the kernel tests assert exact equality.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compress.codecs import _QMAX as _CODEC_QMAX
from repro.kernels.payload_gather import (
    _UNREAD, _at_index, _at_step, _row_view,
)

_QMAX = float(_CODEC_QMAX[8])      # symmetric int8 grid, shared w/ codec

# explicit oracle registry (analysis rule `kernel-parity`): every public
# kernel here maps onto its pure-jnp twin in kernels/ref.py
PARITY_ORACLES = {
    "gather_dequant_rows": "gather_dequant_rows_ref",
    "gather_dequant_rows_block": "gather_dequant_rows_block_ref",
    "quant_scatter_set_rows": "quant_scatter_set_rows_ref",
    "quant_scatter_set_rows_block": "quant_scatter_set_rows_block_ref",
}


def _gather_dequant_kernel(idx_ref, codes_ref, scales_ref, out_ref):
    # codes/scales blocks are the (1, 1, K) / (1, 1, 1) rows at idx[i]
    del idx_ref
    out_ref[...] = codes_ref[...].astype(jnp.float32) * scales_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_dequant_rows(
    codes: jax.Array,      # (M, K) int8 moment codes
    scales: jax.Array,     # (M, 1) float32 per-row scales
    idx: jax.Array,        # (M_s,) int32 unique row ids
    *,
    interpret: bool = False,
) -> jax.Array:
    """Fused moment read: ``out[i] = codes[idx[i]] * scales[idx[i]]``.

    Returns the float32 (M_s, K) tile of the selected rows' dequantized
    moments, one pass over the stored int8 rows.
    """
    m_s = idx.shape[0]
    k = codes.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m_s,),
        in_specs=[_at_index(k), _at_index(1)],
        out_specs=_at_step(k),
    )
    return pl.pallas_call(
        _gather_dequant_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_s, 1, k), jnp.float32),
        interpret=interpret,
    )(idx.astype(jnp.int32), _row_view(codes), _row_view(scales)).reshape(
        m_s, k)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_dequant_rows_block(
    codes: jax.Array,      # (m, K) — one shard's row block of the codes
    scales: jax.Array,     # (m, 1) — matching scale block
    local_idx: jax.Array,  # (M_s,) shard-local row ids; may be out of range
    *,
    interpret: bool = False,
) -> jax.Array:
    """Shard-local fused moment read over a row-sharded int8 table.

    Identical to :func:`gather_dequant_rows` on ``clip(local_idx)`` —
    out-of-range rows are clamp artifacts discarded by the owner-select
    after the all-gather, exactly like every other block gather.
    """
    m = codes.shape[0]
    safe = jnp.clip(local_idx.astype(jnp.int32), 0, m - 1)
    return gather_dequant_rows(codes, scales, safe, interpret=interpret)


def _quant_scatter_kernel(idx_ref, rows_ref, codes_in, scales_in,
                          codes_out, scales_out):
    # aliased in/out: overwrite the stored row with the requantized tile.
    del idx_ref, codes_in, scales_in
    row = rows_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(row), axis=-1, keepdims=True)      # (1, 1, 1)
    scale = absmax * (1.0 / _QMAX)   # matches codecs.quantize_rows exactly
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    codes_out[...] = jnp.clip(
        jnp.round(row * inv), -_QMAX, _QMAX).astype(jnp.int8)
    scales_out[...] = scale


def _quant_scatter_sr_kernel(idx_ref, rows_ref, noise_ref, codes_in,
                             scales_in, codes_out, scales_out):
    # stochastic variant: floor(x/scale + u) — codecs.quantize_rows_stochastic
    del idx_ref, codes_in, scales_in
    row = rows_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(row), axis=-1, keepdims=True)      # (1, 1, 1)
    scale = absmax * (1.0 / _QMAX)
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    codes_out[...] = jnp.clip(
        jnp.floor(row * inv + noise_ref[...].astype(jnp.float32)),
        -_QMAX, _QMAX).astype(jnp.int8)
    scales_out[...] = scale


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0, 1))
def quant_scatter_set_rows(
    codes: jax.Array,      # (M, K) int8 — donated, updated in place
    scales: jax.Array,     # (M, 1) float32 — donated, updated in place
    idx: jax.Array,        # (M_s,) unique row ids
    rows: jax.Array,       # (M_s, K) float32 updated moment tile
    noise=None,            # optional (M_s, K) U[0,1) stochastic dither
    *,
    interpret: bool = False,
):
    """Fused moment write: ``(codes[idx[i]], scales[idx[i]]) =
    quantize(rows[i])``, stochastic when ``noise`` is given.

    Requantize-and-patch of the updated fp32 tile into the resident int8
    moment table, aliased so no O(M*K) copy is ever made.
    """
    m_s = idx.shape[0]
    k = codes.shape[1]
    out_shape = (
        jax.ShapeDtypeStruct(_row_view(codes).shape, jnp.int8),
        jax.ShapeDtypeStruct(_row_view(scales).shape, jnp.float32),
    )
    # the payload tile (and the dither, when given) streams per grid step;
    # the resident codes/scales are only written, at idx[i]
    tiles = [rows] if noise is None else [rows, noise]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(m_s,),
        in_specs=[_at_step(k)] * len(tiles) + [_UNREAD, _UNREAD],
        out_specs=[_at_index(k), _at_index(1)],
    )
    n = 1 + len(tiles)                 # args: idx, *tiles, codes, scales
    new_codes, new_scales = pl.pallas_call(
        _quant_scatter_kernel if noise is None else _quant_scatter_sr_kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases={n: 0, n + 1: 1},
        interpret=interpret,
    )(idx.astype(jnp.int32), *map(_row_view, tiles), _row_view(codes),
      _row_view(scales))
    return new_codes.reshape(codes.shape), new_scales.reshape(scales.shape)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0, 1))
def quant_scatter_set_rows_block(
    codes: jax.Array,      # (m, K) int8 — one shard's row block, donated
    scales: jax.Array,     # (m, 1) float32 — matching scale block, donated
    local_idx: jax.Array,  # (M_s,) shard-local row ids; out-of-range dropped
    rows: jax.Array,       # (M_s, K) float32 updated moment tile
    noise=None,            # optional (M_s, K) U[0,1) stochastic dither
    *,
    interpret: bool = False,
):
    """Shard-local fused moment write: in-range rows requantized+written,
    out-of-range entries (rows owned by another shard) dropped.

    Same stable in-range compaction as
    :func:`repro.kernels.payload_gather.scatter_set_rows_block` — masked
    grid steps repeat the last in-range entry with its own values, so
    duplicate writes are idempotent and no step touches a foreign row.
    """
    m_s = local_idx.shape[0]
    m = codes.shape[0]
    local_idx = local_idx.astype(jnp.int32)
    valid = (local_idx >= 0) & (local_idx < m)
    n_valid = jnp.sum(valid.astype(jnp.int32))
    perm = jnp.argsort(jnp.where(valid, 0, 1).astype(jnp.int32))
    safe = perm[jnp.minimum(jnp.arange(m_s), n_valid - 1)]
    idx_safe = jnp.clip(local_idx[safe], 0, m - 1)
    rows_safe = rows[safe]
    noise_safe = None if noise is None else noise[safe]

    def commit(ops_in):
        c, s = ops_in
        return quant_scatter_set_rows(c, s, idx_safe, rows_safe, noise_safe,
                                      interpret=interpret)

    return jax.lax.cond(n_valid > 0, commit, lambda ops_in: ops_in,
                        (codes, scales))
