"""Public jit'd kernel entry points.

Backend dispatch:
  * TPU: compiled Pallas kernels.
  * CPU + REPRO_INTERPRET=1: Pallas interpret mode (kernel body in Python) —
    what the kernel tests exercise.
  * CPU default: the jnp oracles (bit-identical semantics, fast on CPU) so
    simulations and benchmarks are not throttled by interpret mode.
  * REPRO_FORCE_REF=1 forces oracles everywhere (A/B a suspected kernel bug).
"""
from __future__ import annotations

import os
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _flash
from repro.kernels import fcf_grad as _fcf
from repro.kernels import moment_quant as _mq
from repro.kernels import payload_gather as _pg
from repro.kernels import payload_quant as _pq
from repro.kernels import payload_score as _ps
from repro.kernels import ref as _ref


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _use_ref() -> bool:
    if os.environ.get("REPRO_FORCE_REF", "0") == "1":
        return True
    on_cpu = jax.default_backend() != "tpu"
    return on_cpu and os.environ.get("REPRO_INTERPRET", "0") != "1"


def fcf_item_gradients(
    q: jax.Array, p: jax.Array, x: jax.Array,
    *, alpha: float = 4.0, l2: float = 1.0, block_m: int = 256,
) -> jax.Array:
    """Fused FCF item gradient (Eqs. 5-6) over an item-blocked grid."""
    if _use_ref():
        return _ref.fcf_grad_ref(q, p, x, l2=l2, alpha=alpha)
    return _fcf.fcf_grad(q, p, x, alpha=alpha, l2=l2, block_m=block_m,
                         interpret=_interpret())


def gather_rows(table: jax.Array, idx: jax.Array) -> jax.Array:
    """Payload download: Q* = Q[idx]."""
    if _use_ref():
        return _ref.gather_rows_ref(table, idx)
    return _pg.gather_rows(table, idx, interpret=_interpret())


def scatter_add_rows(table: jax.Array, idx: jax.Array, rows: jax.Array) -> jax.Array:
    """Payload upload: Q[idx] += rows. ``idx`` must be unique."""
    if _use_ref():
        return _ref.scatter_add_rows_ref(table, idx, rows)
    return _pg.scatter_add_rows(table, idx, rows, interpret=_interpret())


def scatter_set_rows(table: jax.Array, idx: jax.Array, rows: jax.Array) -> jax.Array:
    """Payload row commit: Q[idx] = rows. ``idx`` must be unique."""
    if _use_ref():
        return _ref.scatter_set_rows_ref(table, idx, rows)
    return _pg.scatter_set_rows(table, idx, rows, interpret=_interpret())


def gather_quantize_rows(table: jax.Array, idx: jax.Array):
    """Fused downlink encode: (int8 codes, f32 scales) = quant(Q[idx])."""
    if _use_ref():
        return _ref.gather_quantize_rows_ref(table, idx)
    return _pq.gather_quantize_rows(table, idx, interpret=_interpret())


# ------------------------------------------------------------------ #
# shard-local (row-block) variants — the per-device halves of the
# collective row ops used by the sharded round engine. ``local_idx``
# is ``global_idx - shard_offset``; out-of-range entries are rows the
# shard does not own (gathers clamp and let the owner-select drop them,
# scatters drop the write).
# ------------------------------------------------------------------ #
def gather_rows_block(table: jax.Array, local_idx: jax.Array) -> jax.Array:
    """Shard-local payload gather over one row block of a sharded table."""
    if _use_ref():
        return _ref.gather_rows_block_ref(table, local_idx)
    return _pg.gather_rows_block(table, local_idx, interpret=_interpret())


def scatter_set_rows_block(
    table: jax.Array, local_idx: jax.Array, rows: jax.Array
) -> jax.Array:
    """Shard-local row commit: in-range rows written, out-of-range dropped."""
    if _use_ref():
        return _ref.scatter_set_rows_block_ref(table, local_idx, rows)
    return _pg.scatter_set_rows_block(table, local_idx, rows,
                                      interpret=_interpret())


def gather_quantize_rows_block(table: jax.Array, local_idx: jax.Array):
    """Shard-local fused gather+int8-quantize over one row block."""
    if _use_ref():
        return _ref.gather_quantize_rows_block_ref(table, local_idx)
    return _pq.gather_quantize_rows_block(table, local_idx,
                                          interpret=_interpret())


# ------------------------------------------------------------------ #
# compressed optimizer-moment row ops (repro.optim.state_compress):
# int8 moment tables are read and written through these fused
# dequant/requant kernels so the full-table fp32 moments never exist.
# ------------------------------------------------------------------ #
def gather_dequant_rows(
    codes: jax.Array, scales: jax.Array, idx: jax.Array
) -> jax.Array:
    """Fused moment read: f32 rows = codes[idx] * scales[idx]."""
    if _use_ref():
        return _ref.gather_dequant_rows_ref(codes, scales, idx)
    return _mq.gather_dequant_rows(codes, scales, idx, interpret=_interpret())


def quant_scatter_set_rows(
    codes: jax.Array, scales: jax.Array, idx: jax.Array, rows: jax.Array,
    noise: Optional[jax.Array] = None,
):
    """Fused moment write: (codes[idx], scales[idx]) = quantize(rows);
    stochastic floor-rounding when ``noise`` (U[0,1) dither) is given."""
    if _use_ref():
        return _ref.quant_scatter_set_rows_ref(codes, scales, idx, rows,
                                               noise)
    return _mq.quant_scatter_set_rows(codes, scales, idx, rows, noise,
                                      interpret=_interpret())


def gather_dequant_rows_block(
    codes: jax.Array, scales: jax.Array, local_idx: jax.Array
) -> jax.Array:
    """Shard-local fused moment read over one row block (clamped gather)."""
    if _use_ref():
        return _ref.gather_dequant_rows_block_ref(codes, scales, local_idx)
    return _mq.gather_dequant_rows_block(codes, scales, local_idx,
                                         interpret=_interpret())


def quant_scatter_set_rows_block(
    codes: jax.Array, scales: jax.Array, local_idx: jax.Array,
    rows: jax.Array, noise: Optional[jax.Array] = None,
):
    """Shard-local fused moment write: out-of-range entries dropped."""
    if _use_ref():
        return _ref.quant_scatter_set_rows_block_ref(codes, scales, local_idx,
                                                     rows, noise)
    return _mq.quant_scatter_set_rows_block(codes, scales, local_idx, rows,
                                            noise, interpret=_interpret())


class RowOps(NamedTuple):
    """Row-granular access to a (possibly row-sharded) (M, K) table.

    The FL round step, the sparse Adam commit and the BTS reward update all
    touch full tables only through gather/scatter of the selected payload
    rows. Abstracting that pair lets the same code run on a resident table
    (``default_row_ops`` — the Pallas/jnp kernels above) or on a row shard
    inside ``shard_map`` (collective-aware ops built by
    :func:`repro.cf.server.shard_row_ops`: local gather -> all-gather ->
    owner-select, and shard-local drop-scatter).

    CONTRACT: ``gather`` returns its rows behind a
    ``lax.optimization_barrier``. The sharded round engine's bit-parity with
    the single-device scan relies on update expressions (Adam moments,
    reward EMAs) compiling against *identical producer graphs* in both
    programs — without the barrier, XLA/LLVM may contract an
    ``a*x + b*y*y`` into an FMA in one fusion context and not the other,
    and the trajectories drift by an ulp per round. Materializing gathered
    rows costs one (M_s, K) buffer and pins the fusion boundary.
    """

    gather: Callable[[jax.Array, jax.Array], jax.Array]
    scatter_set: Callable[[jax.Array, jax.Array, jax.Array], jax.Array]


def default_row_ops() -> RowOps:
    """Row ops over a fully-resident table (the single-device hot path)."""
    from jax.lax import optimization_barrier

    def gather(table: jax.Array, idx: jax.Array) -> jax.Array:
        return optimization_barrier(gather_rows(table, idx))

    return RowOps(gather=gather, scatter_set=scatter_set_rows)


def dequant_scatter_set_rows(
    table: jax.Array, idx: jax.Array, values: jax.Array, scales: jax.Array
) -> jax.Array:
    """Fused wire commit: Q[idx] = dequant(values, scales). Unique ``idx``."""
    if _use_ref():
        return _ref.dequant_scatter_set_rows_ref(table, idx, values, scales)
    return _pq.dequant_scatter_set_rows(table, idx, values, scales,
                                        interpret=_interpret())


# the v5e's default scoped-VMEM limit: what one kernel invocation may hold
_VMEM_BUDGET = 16 * 1024 * 1024


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def fit_block_m(batch: int, dim: int, top_n: int) -> int:
    """Largest item block (a power of two in [128, 4096]) whose scoring tile
    fits the scoped VMEM at this batch and row width.

    A block holds ~8 live (B, block_m + N) 32-bit arrays (the masked score
    tile, its double-buffered mask block, and the merge's candidate/select
    temporaries) plus ~4 (block_m, K) 32-bit arrays (the double-buffered
    wire block and its dequantized copy), each padded to whole (8, 128)
    tiles. The estimate is conservative: it admits block 1024 and refuses
    2048 at B=256, K=25, where the compiler accepts 2048 and refuses 4096.
    """
    b8 = -(-batch // 8) * 8
    block = 4096
    while block > 128 and 4 * (8 * b8 * _lanes(block + top_n)
                               + 4 * block * _lanes(dim)) > _VMEM_BUDGET:
        block //= 2
    return block


def wire_topn(
    cfg,                   # repro.compress.CodecConfig
    wire,                  # full-table wire pytree (row-leading leaves)
    p: jax.Array,          # (B, K) user factors
    dim: int,              # K — decoded row width
    top_n: int,
    train_mask: Optional[jax.Array] = None,   # (B, M) binary; 1 = exclude
    *,
    block_m: int = 1024,
):
    """Fused dequant->score->top-N over a COMPRESSED table: the serving read
    path. Returns ``(scores (B, N) f32, item ids (B, N) i32)`` in descending
    score order with ``lax.top_k`` tie semantics (equal scores -> lowest id).

    Neither the dense fp32 table nor the (B, M) score matrix is ever
    materialized. The topk wire format has no block-dequant kernel (sparse
    scatter, not a row transform) and always takes the chunked oracle.
    """
    if _use_ref() or cfg.name == "topk":
        return _ref.wire_topn_ref(cfg, wire, p, dim, top_n,
                                  train_mask=train_mask, block_m=block_m)
    interp = _interpret()
    if cfg.name in ("fp32", "fp16"):
        return _ps.dense_topn(p, wire.values, top_n, train_mask,
                              block_m=block_m, interpret=interp)
    if cfg.name == "int8":
        return _ps.quant_topn(p, wire.values, wire.scales, top_n, train_mask,
                              block_m=block_m, interpret=interp)
    if cfg.name == "int4":
        return _ps.quant4_topn(p, wire.values, wire.scales, dim, top_n,
                               train_mask, block_m=block_m, interpret=interp)
    raise ValueError(f"no fused scoring path for codec {cfg.name!r}")


def attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 256,
) -> jax.Array:
    """Grouped-query flash attention (B, H, S, D) x (B, KVH, T, D)."""
    if _use_ref():
        # long sequences: chunked online-softmax oracle so the compiled HLO
        # has flash-like O(S*chunk) memory (dry-run fidelity + CPU memory)
        if q.shape[2] * k.shape[2] > 1024 * 2048:
            return _ref.mha_chunked_ref(q, k, v, causal=causal, window=window,
                                        q_offset=q_offset)
        return _ref.mha_ref(q, k, v, causal=causal, window=window,
                            q_offset=q_offset)
    return _flash.flash_attention(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, interpret=_interpret())
