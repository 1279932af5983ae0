"""Fused FCF item-gradient Pallas kernel.

The CF compute hot spot (Eqs. 5-6): for a cohort of B users and a payload of
M items, the server-side naive formulation materializes the (B, M) residual
and confidence matrices in HBM (for production M up to 10^7 that is GBs per
cohort). This kernel blocks over items, fusing residual computation,
confidence weighting and the gradient matmul inside VMEM, so HBM traffic is
O(B*K + M*K) instead of O(B*M).

TPU mapping:
  * grid = (ceil(M / block_m),) — one program per item block,
  * per block: x_blk (B, bm) and q_blk (bm, K) stream through VMEM, p (B, K)
    is resident (small: cohort x factors),
  * the two MXU contractions per block are (B,K)x(K,bm) and (bm,B)x(B,K);
    choose block_m a multiple of 128 (lane dim) and pad K to 128 at the
    wrapper for MXU alignment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fcf_grad_kernel(p_ref, q_ref, x_ref, out_ref, *, alpha: float, l2: float,
                     batch: int):
    """One item block: out = -2 (c . e)^T P + 2 l2 B q."""
    p = p_ref[...].astype(jnp.float32)          # (B, K)
    q = q_ref[...].astype(jnp.float32)          # (bm, K)
    x = x_ref[...].astype(jnp.float32)          # (B, bm)

    # float32-accurate MXU passes on the TPU (interpret mode ignores it)
    hi = jax.lax.Precision.HIGHEST
    pred = jax.lax.dot_general(                  # (B, bm) = P @ q_blk^T
        p, q, (((1,), (1,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)
    err = x - pred
    weighted = (1.0 + alpha * x) * err           # confidence-weighted residual
    grad = jax.lax.dot_general(                  # (bm, K) = weighted^T @ P
        weighted, p, (((0,), (0,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)
    out_ref[...] = (-2.0 * grad + (2.0 * l2 * batch) * q).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("alpha", "l2", "block_m", "interpret"))
def fcf_grad(
    q: jax.Array,            # (M, K)
    p: jax.Array,            # (B, K)
    x: jax.Array,            # (B, M)
    *,
    alpha: float = 4.0,
    l2: float = 1.0,
    block_m: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Blocked fused item gradient. Pads M to a block multiple internally."""
    m, k = q.shape
    b = p.shape[0]
    m_pad = (m + block_m - 1) // block_m * block_m
    if m_pad != m:
        q = jnp.pad(q, ((0, m_pad - m), (0, 0)))
        x = jnp.pad(x, ((0, 0), (0, m_pad - m)))

    grid = (m_pad // block_m,)
    out = pl.pallas_call(
        functools.partial(_fcf_grad_kernel, alpha=alpha, l2=l2, batch=b),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, k), lambda i: (0, 0)),          # p resident
            pl.BlockSpec((block_m, k), lambda i: (i, 0)),    # q block
            pl.BlockSpec((b, block_m), lambda i: (0, i)),    # x block
        ],
        out_specs=pl.BlockSpec((block_m, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, k), q.dtype),
        interpret=interpret,
    )(p, q, x)
    return out[:m]
