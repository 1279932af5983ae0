"""Fused dequant -> score -> top-N Pallas kernels: the serving read path.

Training optimizes the write path (which rows move); production FRS traffic
is dominated by recommendation READS. The serving hot loop is

    top-N( mask( P @ decode(wire_table).T ) )

and a naive implementation materializes two tensors the paper's compressed
deployment model says should never exist: the dense fp32 item table
(decode of the whole wire image) and the (B, M) score matrix. These kernels
fuse all three stages over item blocks:

  * one grid step per (block_m, K) row block of the WIRE table — the block
    is dequantized in VMEM (int8/int4 per-row-scale, fp16 widen, fp32
    passthrough), scored against the resident (B, K) user factors on the
    MXU, train-masked, and folded into a running per-user top-N carried in
    the output refs. HBM traffic is one pass over the compressed table
    (4x/~7x fewer bytes than fp32 for int8/int4) plus the (B, N) results;
    peak VMEM is one block + one (B, block_m) score tile.
  * the top-N merge is N unrolled rounds of vectorized first-argmax
    selection over [running top-N | block scores], which reproduces
    ``lax.top_k``'s stable tie rule (equal scores -> lowest item id first)
    exactly — see ``ref.topn_merge_ref`` for the induction argument.

BIT-EXACTNESS CONTRACT (same shape as payload_quant's): dequantization
reproduces :mod:`repro.compress.codecs` op-for-op, scores reduce over K
only (item blocking cannot reorder a dot product), and the merge preserves
top_k tie order — so fp32/fp16/int8/int4 results are bit-identical to
``ref.wire_topn_ref``, values AND indices AND order, in interpret mode. On
the TPU the score matmul runs at ``Precision.HIGHEST`` (float32 accuracy);
XLA's own default for an f32 dot is coarser, so the on-chip oracle is run
under ``jax.default_matmul_precision("highest")`` and agrees to float32
rounding, not bit-for-bit. The topk wire format has no kernel (scoring a sparse
wire is a scatter, not a block dequant) and always routes through the ref.

Masking uses the metrics module's ``NEG_INF`` (-1e30) sentinel, so a
train-interaction mask here ranks identically to ``cf.metrics
.ranked_metrics`` — the kernel can back ranked evaluation, not just
serving.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# kernel -> ref.py oracle, for kernels whose oracle is not `<name>_ref`:
# all three codec variants share the one wire-level oracle (repro.analysis
# kernel-parity reads this mapping)
PARITY_ORACLES = {
    "dense_topn": "wire_topn_ref",
    "quant_topn": "wire_topn_ref",
    "quant4_topn": "wire_topn_ref",
}

NEG_INF = -1e30     # train-mask sentinel, shared with repro.cf.metrics


def _unpack_int4_block(packed: jax.Array, dim: int) -> jax.Array:
    """In-VMEM nibble unpack to float32 codes, value-for-value
    ``codecs.unpack_int4``.

    Byte j holds code 2j in its low nibble and code 2j+1 in its high one
    (``codecs.pack_int4``). Interleaving the two halves with a stack and a
    reshape is a shape cast Mosaic refuses, so each half is placed into its
    columns by a 0/1 matrix product instead: every output is one code (an
    integer in [-7, 7]) times 1.0 plus zeros, exact at any matmul precision.
    """
    nib = packed.astype(jnp.int32)
    lo = nib & 0xF
    hi = (nib >> 4) & 0xF
    lo = jnp.where(lo > 7, lo - 16, lo).astype(jnp.float32)
    hi = jnp.where(hi > 7, hi - 16, hi).astype(jnp.float32)
    shape = (packed.shape[1], dim)
    byte = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    even = (col == 2 * byte).astype(jnp.float32)
    odd = (col == 2 * byte + 1).astype(jnp.float32)
    return (jnp.dot(lo, even, preferred_element_type=jnp.float32)
            + jnp.dot(hi, odd, preferred_element_type=jnp.float32))


def _merge_topn(vals, idxs, s, gidx, top_n: int):
    """N rounds of first-argmax selection over [carry | block] candidates.

    Returns the new (B, N) running top — bit-equal to
    ``lax.top_k(concat([vals, s]), N)`` re-gathered through the candidate
    ids: each round takes the FIRST unpicked position holding the row max
    (ties -> lowest position -> carry before block -> lower item id), which
    is exactly top_k's documented stable order. Selection only moves values
    (no arithmetic), so merged scores are the block scores bit-for-bit.
    """
    b = s.shape[0]
    cand_v = jnp.concatenate([vals, s], axis=1)
    cand_i = jnp.concatenate([idxs, gidx], axis=1)
    c = cand_v.shape[1]
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (b, c), 1)
    col_n = jax.lax.broadcasted_iota(jnp.int32, (b, top_n), 1)
    picked = jnp.zeros((b, c), jnp.bool_)
    new_v = jnp.zeros((b, top_n), jnp.float32)
    new_i = jnp.zeros((b, top_n), jnp.int32)
    for n in range(top_n):
        avail = jnp.where(picked, -jnp.inf, cand_v)
        row_max = jnp.max(avail, axis=1, keepdims=True)          # (B, 1)
        hit = (avail == row_max) & ~picked
        pos = jnp.min(jnp.where(hit, iota_c, c), axis=1, keepdims=True)
        at = iota_c == pos
        val_n = jnp.max(jnp.where(at, cand_v, -jnp.inf), axis=1,
                        keepdims=True)
        idx_n = jnp.sum(jnp.where(at, cand_i, 0), axis=1, keepdims=True)
        new_v = jnp.where(col_n == n, val_n, new_v)
        new_i = jnp.where(col_n == n, idx_n, new_i)
        picked = picked | at
    return new_v, new_i


def _make_score_kernel(kind: str, masked: bool, num_rows: int, dim: int,
                       top_n: int, block_m: int):
    """Kernel body for one wire layout; refs arrive [p, wire..., mask?, outs]."""
    n_wire = 1 if kind == "dense" else 2

    def dequant(wire_refs) -> jax.Array:
        if kind == "dense":
            return wire_refs[0][...].astype(jnp.float32)
        codes_ref, scales_ref = wire_refs
        if kind == "int4":
            codes = _unpack_int4_block(codes_ref[...], dim)
        else:
            codes = codes_ref[...].astype(jnp.float32)
        # op-for-op codecs.dequantize_rows: codes f32 * per-row f32 scale
        return codes * scales_ref[...]

    def kernel(*refs):
        p_ref = refs[0]
        wire_refs = refs[1:1 + n_wire]
        mask_ref = refs[1 + n_wire] if masked else None
        vals_ref, idx_ref = refs[-2], refs[-1]
        j = pl.program_id(0)

        @pl.when(j == 0)
        def _init():
            vals_ref[...] = jnp.full(vals_ref.shape, -jnp.inf, jnp.float32)
            idx_ref[...] = jnp.zeros(idx_ref.shape, jnp.int32)

        q = dequant(wire_refs)                                  # (bm, K) f32
        s = jnp.dot(p_ref[...].astype(jnp.float32), q.T,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)         # (B, bm)
        b = s.shape[0]
        gidx = j * block_m + jax.lax.broadcasted_iota(
            jnp.int32, (b, block_m), 1)
        if masked:
            s = jnp.where(mask_ref[...] > 0, NEG_INF, s)
        # rows past the true table end (grid padding) can never win
        s = jnp.where(gidx < num_rows, s, -jnp.inf)
        new_v, new_i = _merge_topn(vals_ref[...], idx_ref[...], s, gidx,
                                   top_n)
        vals_ref[...] = new_v
        idx_ref[...] = new_i

    return kernel


def _call_topn(kind, p, wire_arrays, mask, top_n, block_m, interpret,
               num_rows, dim):
    b, _ = p.shape
    nb = -(-num_rows // block_m)
    wire_specs = [
        pl.BlockSpec((block_m, a.shape[1]), lambda j: (j, 0))
        for a in wire_arrays
    ]
    in_specs = [pl.BlockSpec(p.shape, lambda j: (0, 0))] + wire_specs
    operands = [p] + list(wire_arrays)
    if mask is not None:
        in_specs.append(pl.BlockSpec((b, block_m), lambda j: (0, j)))
        operands.append(mask)
    kernel = _make_score_kernel(kind, mask is not None, num_rows, dim,
                                top_n, block_m)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((b, top_n), lambda j: (0, 0)),
            pl.BlockSpec((b, top_n), lambda j: (0, 0)),
        ],
        out_shape=(
            jax.ShapeDtypeStruct((b, top_n), jnp.float32),
            jax.ShapeDtypeStruct((b, top_n), jnp.int32),
        ),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit,
                   static_argnames=("top_n", "block_m", "interpret"))
def dense_topn(
    p: jax.Array,          # (B, K) user factors
    values: jax.Array,     # (M, K) fp32/fp16 table (DenseWire.values)
    top_n: int,
    mask: Optional[jax.Array] = None,    # (B, M) binary; 1 = exclude
    *,
    block_m: int = 1024,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused score+top-N over a dense (possibly fp16) wire table."""
    return _call_topn("dense", p, (values,), mask, top_n, block_m,
                      interpret, values.shape[0], values.shape[1])


@functools.partial(jax.jit,
                   static_argnames=("top_n", "block_m", "interpret"))
def quant_topn(
    p: jax.Array,          # (B, K)
    codes: jax.Array,      # (M, K) int8 codes (QuantWire.values)
    scales: jax.Array,     # (M, 1) float32 per-row scales
    top_n: int,
    mask: Optional[jax.Array] = None,
    *,
    block_m: int = 1024,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused int8 dequant+score+top-N — never materializes fp32 rows."""
    return _call_topn("int8", p, (codes, scales), mask, top_n, block_m,
                      interpret, codes.shape[0], codes.shape[1])


@functools.partial(jax.jit,
                   static_argnames=("dim", "top_n", "block_m", "interpret"))
def quant4_topn(
    p: jax.Array,          # (B, K)
    packed: jax.Array,     # (M, ceil(K/2)) uint8 nibble pairs
    scales: jax.Array,     # (M, 1) float32
    dim: int,              # K (the unpacked row width)
    top_n: int,
    mask: Optional[jax.Array] = None,
    *,
    block_m: int = 1024,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused int4 unpack+dequant+score+top-N."""
    return _call_topn("int4", p, (packed, scales), mask, top_n, block_m,
                      interpret, packed.shape[0], dim)
