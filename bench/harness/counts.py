"""The algorithm's operations and bytes, counted from shapes.

These count the work the method needs, not what an implementation happens
to do: a padded, relaid-out or recomputed byte or operation does not count,
so waste shows as a lower share of the roofline. Symbols: Theta users in a
round's cohort, M items, M_s payload rows per round, K factors, B users in a
scoring request, N items returned per user.
"""
from __future__ import annotations

F32 = 4
INT8 = 1


def num_select(num_items: int, strategy: str, keep_fraction: float) -> int:
    if strategy == "full":
        return num_items
    return max(1, int(round(keep_fraction * num_items)))


def solve_ops(theta: int, m_s: int, k: int) -> float:
    """Eq. 3 for every cohort user against the dense (Theta, M_s) rows:
    the shared Gram Q*^T Q*, each user's confidence correction over the
    K(K+1)/2 upper triangle, the right-hand side, a Cholesky factorization
    and two triangular solves."""
    tri = k * (k + 1) // 2
    return (2.0 * m_s * k * k            # Gram
            + m_s * tri                  # q_j q_j^T upper triangles
            + 2.0 * theta * m_s * tri    # sum_j x_ij (q_j q_j^T)
            + 2.0 * theta * m_s * k      # Q* C_i x_i
            + theta * k ** 3 / 3.0       # Cholesky
            + 2.0 * theta * k * k)       # forward + back substitution


def fcf_grad_ops(theta: int, m_s: int, k: int) -> float:
    """Eqs. 5-6 summed over the cohort: the prediction P Q*^T, the
    confidence-weighted residual, its product with P, and the ridge."""
    return (2.0 * theta * m_s * k        # P Q*^T
            + 3.0 * theta * m_s          # (1 + alpha x)(x - pred)
            + 2.0 * theta * m_s * k      # weighted^T P
            + 3.0 * m_s * k)             # -2 g + 2 l2 Theta q


def fcf_grad_bytes(theta: int, m_s: int, k: int) -> float:
    """The kernel's inputs P (Theta, K), Q* (M_s, K), X (Theta, M_s) and its
    output (M_s, K), float32, each moved once."""
    return F32 * (theta * k + 2.0 * m_s * k + theta * m_s)


def round_ops(num_items: int, m_s: int, theta: int, k: int,
              strategy: str) -> float:
    """Operations of one federated round: selection, int8 wire both ways,
    cohort solve, gradient, sparse Adam commit and (BTS) reward update."""
    ops = 0.0
    if strategy == "bts":
        ops += 8.0 * num_items               # posterior + one sample per arm
    ops += 2 * 4.0 * m_s * k                 # int8 encode + decode, down
    ops += solve_ops(theta, m_s, k)
    ops += fcf_grad_ops(theta, m_s, k)
    ops += 2 * 4.0 * m_s * k                 # int8 encode + decode, up
    ops += 12.0 * m_s * k                    # Adam moments, bias, step
    if strategy == "bts":
        ops += 14.0 * m_s * k + 8.0 * m_s    # Eq. 14 EMA, Eq. 13, standardize
    return ops


# bytes each payload row kernel moves per row of K values, by the name the
# compiled program gives it: what it reads plus what it writes
_ROW_BYTES_PER_ROW = {
    "gather_rows": lambda k: 2 * F32 * k,
    "scatter_set_rows": lambda k: 2 * F32 * k,
    "scatter_add_rows": lambda k: 3 * F32 * k,
    "gather_quantize_rows": lambda k: F32 * k + INT8 * k + F32,
    "dequant_scatter_set_rows": lambda k: INT8 * k + F32 + F32 * k,
    "gather_dequant_rows": lambda k: INT8 * k + F32 + F32 * k,
    "quant_scatter_set_rows": lambda k: F32 * k + INT8 * k + F32,
}
ROW_KERNELS = tuple(_ROW_BYTES_PER_ROW)


def row_kernel_bytes(kernel: str, rows: int, k: int) -> float:
    return rows * _ROW_BYTES_PER_ROW[kernel](k)


def score_ops(b: int, m: int, k: int) -> float:
    """Scoring B users against M items: the (B, M) dot products, with the
    int8 dequantization of each row counted once per call."""
    return 2.0 * b * m * k + 2.0 * m * k


def score_bytes(b: int, m: int, k: int, n: int) -> float:
    """An int8 table with per-row scales, a float32 (B, M) seen-item mask,
    the (B, K) user factors in, and (B, N) scores and ids out."""
    return (INT8 * m * k + F32 * m + F32 * b * m + F32 * b * k
            + 2 * F32 * b * n)
