"""Fused payload compression Pallas kernels.

With a quantized wire format the FL server's per-round hot path becomes:

  * downlink: Q*[wire] = quantize(Q[idx])  — gather M_s of M rows AND
    quantize them, fused into one kernel so each selected row makes a
    single HBM->VMEM trip and leaves VMEM already in wire format
    (:func:`gather_quantize_rows`).
  * uplink/commit: table[idx] = dequantize(wire rows) — dequantize the
    received int8 rows and scatter them into the resident float32 table in
    one kernel, aliased in place (:func:`dequant_scatter_set_rows`). This
    is the client-side patch-in of a quantized downlink (the client's
    local model is the server model with the fresh rows written over it)
    and the server-side commit of wire-format row payloads.

Same structure as :mod:`repro.kernels.payload_gather`: one grid step per
selected row, scalar-prefetched indices so the index_map can steer the row
DMA, (1, 1, K) blocks over the (M, 1, K) row view.

BIT-EXACTNESS CONTRACT: the quantization math here must reproduce
:func:`repro.compress.codecs.quantize_rows` / ``dequantize_rows``
bit-for-bit (same op sequence: absmax -> scale = absmax/qmax ->
codes = clip(round(x * (1/scale)))), so a kernel-routed round and a
pure-codec round produce identical trajectories. ``kernels/ref.py``
delegates to the codec functions and the kernel tests assert exact
equality against those refs.
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


def _gather_quant_kernel(idx_ref, table_ref, values_ref, scales_ref):
    # table_ref block is (1, 1, K) at row idx[i] — selected by the index_map.
    row = table_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(row), axis=-1, keepdims=True)      # (1, 1, 1)
    scale = absmax * (1.0 / _QMAX)   # matches codecs.quantize_rows exactly
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    values_ref[...] = jnp.clip(
        jnp.round(row * inv), -_QMAX, _QMAX).astype(jnp.int8)
    scales_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_quantize_rows(
    table: jax.Array,      # (M, K) float table
    idx: jax.Array,        # (M_s,) int32 unique row ids
    *,
    interpret: bool = False,
):
    """Fused downlink encode: ``(codes, scales) = quantize(table[idx])``.

    Returns ``codes`` int8 (M_s, K) and ``scales`` float32 (M_s, 1) — the
    int8 wire image of the selected payload rows, produced in one pass
    over the gathered rows instead of gather-then-quantize.
    """
    m_s = idx.shape[0]
    k = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m_s,),
        in_specs=[_at_index(k)],
        out_specs=[_at_step(k), _at_step(1)],
    )
    codes, scales = pl.pallas_call(
        _gather_quant_kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((m_s, 1, k), jnp.int8),
            jax.ShapeDtypeStruct((m_s, 1, 1), jnp.float32),
        ),
        interpret=interpret,
    )(idx.astype(jnp.int32), _row_view(table))
    return codes.reshape(m_s, k), scales.reshape(m_s, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_quantize_rows_block(
    table: jax.Array,      # (m, K) — one shard's row block of a larger table
    local_idx: jax.Array,  # (M_s,) shard-local row ids; may be out of range
    *,
    interpret: bool = False,
):
    """Shard-local fused downlink encode over a row-sharded table.

    Identical to :func:`gather_quantize_rows` on ``clip(local_idx)``: every
    shard produces a full (M_s,) wire candidate block (int8 codes + scales)
    whose rows it does not own are clamp artifacts, discarded by the
    owner-select after the all-gather. Because quantization is per-row, the
    rows a shard *does* own carry exactly the codes/scales a single-device
    encode of the full table would produce — so the collective moves the
    already-quantized wire image (4x fewer bytes than fp32 rows) without
    giving up bit-parity with the unsharded path.
    """
    m = table.shape[0]
    safe = jnp.clip(local_idx.astype(jnp.int32), 0, m - 1)
    return gather_quantize_rows(table, safe, interpret=interpret)


def _dequant_scatter_kernel(idx_ref, values_ref, scales_ref, table_in_ref,
                            out_ref):
    # aliased in/out: overwrite the table row with the dequantized payload.
    del table_in_ref
    row = values_ref[...].astype(jnp.float32) * scales_ref[...]
    out_ref[...] = row.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def dequant_scatter_set_rows(
    table: jax.Array,      # (M, K) — donated and updated in place
    idx: jax.Array,        # (M_s,) unique row ids
    values: jax.Array,     # (M_s, K) int8 codes
    scales: jax.Array,     # (M_s, 1) float32 per-row scales
    *,
    interpret: bool = False,
) -> jax.Array:
    """Fused wire commit: ``table[idx[i]] = values[i] * scales[i]``.

    The dequantize-and-patch of a quantized row payload into a resident
    float table, aliased so no O(M*K) copy is made.
    """
    m_s = idx.shape[0]
    k = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m_s,),
        in_specs=[_at_step(k), _at_step(1), _UNREAD],  # values, scales, table
        out_specs=_at_index(k),
    )
    return pl.pallas_call(
        _dequant_scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(_row_view(table).shape, table.dtype),
        # alias the table operand (positional arg 3: idx, values, scales, table)
        input_output_aliases={3: 0},
        interpret=interpret,
    )(idx.astype(jnp.int32), _row_view(values), _row_view(scales),
      _row_view(table)).reshape(table.shape)
