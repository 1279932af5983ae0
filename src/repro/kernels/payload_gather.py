"""Payload row gather / scatter-add Pallas kernels.

The payload subset operations are the per-round hot path of the FL server:
  * download: Q* = Q[idx]            (gather M_s of M rows)
  * upload:   Q[idx] += grad_rows    (scatter-add aggregated gradients)

For LLM-scale tables (256k x 5120) these run every round; each grid step
moves one row, and scalar prefetch makes the row indices available to the
index_map before the DMA is issued — the TPU-native equivalent of the
paper's "subset the Q factor matrix".

ROW VIEW. Mosaic requires a block's last two dims to be multiples of
(8, 128) or equal to the array's, so a (1, K) row block of an (M, K) table
is refused for every K the repo uses (25, 16). Every row kernel therefore
runs over the free reshape ``(M, K) -> (M, 1, K)`` (:func:`_row_view`) with
``(1, 1, K)`` blocks, whose last two dims equal the array's.

Note on scatter semantics: indices are assumed UNIQUE (payload selections
are top-k / choice-without-replacement, so this holds by construction).
TPU grids execute sequentially so revisiting would still be correct, but
uniqueness is asserted in the ops.py wrapper for defense in depth.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _row_view(a: jax.Array) -> jax.Array:
    """(M, K) -> (M, 1, K): the layout every row kernel blocks over."""
    return a.reshape(a.shape[0], 1, a.shape[1])


def _at_index(width: int) -> pl.BlockSpec:
    """The (1, 1, width) row block at ``idx[i]`` (scalar-prefetched)."""
    return pl.BlockSpec((1, 1, width), lambda i, idx_ref: (idx_ref[i], 0, 0))


def _at_step(width: int) -> pl.BlockSpec:
    """The (1, 1, width) row block at grid step ``i`` (the payload side)."""
    return pl.BlockSpec((1, 1, width), lambda i, idx_ref: (i, 0, 0))


# an aliased output's input operand that the kernel never reads: left in
# place (no DMA), every row the grid does not write keeps its value
_UNREAD = pl.BlockSpec(memory_space=pl.ANY)


def _gather_kernel(idx_ref, table_ref, out_ref):
    # table_ref block is (1, 1, K) at row idx[i] — selected by the index_map.
    out_ref[...] = table_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(
    table: jax.Array,      # (M, K)
    idx: jax.Array,        # (M_s,) int32 unique row ids
    *,
    interpret: bool = False,
) -> jax.Array:
    """out[i] = table[idx[i]] via scalar-prefetch indexed DMA."""
    m_s = idx.shape[0]
    k = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m_s,),
        in_specs=[_at_index(k)],
        out_specs=_at_step(k),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_s, 1, k), table.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), _row_view(table)).reshape(m_s, k)


def _scatter_set_kernel(idx_ref, rows_ref, table_in_ref, out_ref):
    # aliased in/out: replace the table row with the payload row.
    del table_in_ref
    out_ref[...] = rows_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def scatter_set_rows(
    table: jax.Array,      # (M, K) — donated and updated in place
    idx: jax.Array,        # (M_s,) unique row ids
    rows: jax.Array,       # (M_s, K)
    *,
    interpret: bool = False,
) -> jax.Array:
    """table[idx[i]] = rows[i]; the table is aliased (no O(M*K) copy).

    The row-replace flavour of :func:`scatter_add_rows` — this is the commit
    path of the payload-selected sparse Adam update, where the server writes
    fully-formed new rows (params and moments) back into the global table.
    """
    m_s = idx.shape[0]
    k = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m_s,),
        in_specs=[_at_step(k), _UNREAD],          # rows, table
        out_specs=_at_index(k),
    )
    return pl.pallas_call(
        _scatter_set_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(_row_view(table).shape, table.dtype),
        # alias the table operand (positional arg 2: idx, rows, table)
        input_output_aliases={2: 0},
        interpret=interpret,
    )(idx.astype(jnp.int32), _row_view(rows), _row_view(table)).reshape(
        table.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows_block(
    table: jax.Array,      # (m, K) — one shard's row block of a larger table
    local_idx: jax.Array,  # (M_s,) shard-local row ids; may be out of range
    *,
    interpret: bool = False,
) -> jax.Array:
    """Shard-local payload gather: ``out[i] = table[clip(local_idx[i])]``.

    The per-device half of a row-sharded table gather: the caller translates
    global payload indices to ``idx - shard_offset`` and every shard gathers
    a full (M_s, K) candidate block — rows it does not own come from the
    clamp and are discarded by the owner-select after the all-gather
    (:func:`repro.cf.server.assemble_rows`). Clamping instead of masking
    keeps the kernel identical to :func:`gather_rows` (one indexed row DMA
    per grid step) with no divergent control flow.
    """
    m = table.shape[0]
    safe = jnp.clip(local_idx.astype(jnp.int32), 0, m - 1)
    return gather_rows(table, safe, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def scatter_set_rows_block(
    table: jax.Array,      # (m, K) — one shard's row block, donated
    local_idx: jax.Array,  # (M_s,) shard-local row ids; out-of-range dropped
    rows: jax.Array,       # (M_s, K)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Shard-local row commit: ``table[local_idx[i]] = rows[i]`` where
    ``0 <= local_idx[i] < m``; out-of-range entries (rows owned by another
    shard) are dropped.

    Built over the :func:`scatter_set_rows` kernel by stably compacting the
    in-range entries to the front and pointing every masked grid step at the
    last in-range entry *with its own row value* — duplicate writes of
    identical data are idempotent under the sequential TPU grid, so no grid
    step ever touches a row this shard does not own and no step can clobber
    an earlier write with stale data. An all-out-of-range call (possible
    when M_s < num_shards) returns the shard unchanged.
    """
    m_s = local_idx.shape[0]
    m = table.shape[0]
    local_idx = local_idx.astype(jnp.int32)
    valid = (local_idx >= 0) & (local_idx < m)
    n_valid = jnp.sum(valid.astype(jnp.int32))
    # stable partition: in-range entries first, original order preserved
    perm = jnp.argsort(jnp.where(valid, 0, 1).astype(jnp.int32))
    safe = perm[jnp.minimum(jnp.arange(m_s), n_valid - 1)]
    idx_safe = jnp.clip(local_idx[safe], 0, m - 1)
    rows_safe = rows[safe]

    def commit(tab):
        return scatter_set_rows(tab, idx_safe, rows_safe, interpret=interpret)

    return jax.lax.cond(n_valid > 0, commit, lambda tab: tab, table)


def _scatter_add_kernel(idx_ref, rows_ref, table_in_ref, out_ref):
    # aliased in/out: accumulate the payload gradient row into the table row.
    out_ref[...] = table_in_ref[...] + rows_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def scatter_add_rows(
    table: jax.Array,      # (M, K) — donated and updated in place
    idx: jax.Array,        # (M_s,) unique row ids
    rows: jax.Array,       # (M_s, K)
    *,
    interpret: bool = False,
) -> jax.Array:
    """table[idx[i]] += rows[i]; the table is aliased (no O(M*K) copy)."""
    m_s = idx.shape[0]
    k = table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m_s,),
        in_specs=[_at_step(k), _at_index(k)],     # rows, table
        out_specs=_at_index(k),
    )
    return pl.pallas_call(
        _scatter_add_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(_row_view(table).shape, table.dtype),
        # alias the table operand (positional arg 2: idx, rows, table)
        input_output_aliases={2: 0},
        interpret=interpret,
    )(idx.astype(jnp.int32), _row_view(rows), _row_view(table)).reshape(
        table.shape)
