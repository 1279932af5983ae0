"""Composite reward function for federated payload selection (Sec. 3.2).

Eq. 13:
  r_t^j = (1 - gamma*t) * cos_sim(v_t^j, grad_t^j)
        + (gamma / t)   * sum_k | grad_{t-1}^j[k] - grad_t^j[k] |

Eq. 14 (Adam-style second-moment EMA):
  v_t^j = beta2 * v_{t-1}^j + (1 - beta2) * grad_t^j**2      [stored]
  vhat_t^j = v_t^j / (1 - beta2**t)                          [used in Eq. 13]

The paper typesets Eq. 14 with a flat "/(1 - beta2)" on the recursion itself.
Applied literally at every iteration that multiplies v by beta2/(1-beta2) = 99
per selection and overflows float32 after ~40 selections (verified by test).
It is clearly intended as Adam's bias correction, which we apply as vhat
(and which is in any case irrelevant to Eq. 13: cosine similarity is
scale-invariant — see test_cosine_invariant_to_paper_v_normalization).

Two readings of the first coefficient are implemented:

  * ``geometric`` (default): (1 - gamma**t). With the paper's gamma=0.999 this
    starts near 0 and grows toward 1 — exactly the behaviour the paper
    describes ("increases the reward ... with the increasing number of FL
    iterations") and keeps rewards bounded.
  * ``paper_literal``: (1 - gamma*t), the literal typeset formula, which is
    negative for every t > 1/gamma ~= 1 and diverges linearly — contradicting
    the stated behaviour. Kept for auditability.

See DESIGN.md §8 for the full rationale.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

_EPS = 1e-12


class RewardState(NamedTuple):
    """Per-arm buffers the reward function needs across FL iterations.

    v:         (M, K) exponential decay of past squared gradients (Eq. 14)
    prev_grad: (M, K) last observed gradient per arm  (nabla^j Q, Alg.1 l.18)
    """

    v: jax.Array
    prev_grad: jax.Array


def reward_init(num_arms: int, dim: int, dtype=jnp.float32) -> RewardState:
    """Algorithm 1 lines 5-6: both buffers initialized to zero."""
    return RewardState(
        v=jnp.zeros((num_arms, dim), dtype),
        prev_grad=jnp.zeros((num_arms, dim), dtype),
    )


def update_v(v_sel: jax.Array, grad_sel: jax.Array, beta2: float = 0.99) -> jax.Array:
    """Eq. 14 EMA recursion for the selected rows. Shapes (M_s, K).

    Stored un-normalized (standard Adam); bias correction is applied at use
    site. The paper's literal per-step "/(1-beta2)" diverges (see module doc).
    """
    return beta2 * v_sel + (1.0 - beta2) * jnp.square(grad_sel)


def _cosine_sim(a: jax.Array, b: jax.Array, axis: int = -1) -> jax.Array:
    num = jnp.sum(a * b, axis=axis)
    den = jnp.linalg.norm(a, axis=axis) * jnp.linalg.norm(b, axis=axis)
    return num / jnp.maximum(den, _EPS)


def compute_rewards(
    state: RewardState,
    indices: jax.Array,   # (M_s,) arms selected this round
    grads: jax.Array,     # (M_s, K) aggregated gradients received for them
    t: jax.Array,         # () current FL iteration, 1-based
    gamma: float = 0.999,
    beta2: float = 0.99,
    mode: str = "geometric",
    row_ops=None,         # optional kernels.ops.RowOps for sharded buffers
    row_mask=None,        # (M_s,) bool — False rows were never observed
) -> Tuple[jax.Array, RewardState]:
    """Rewards for the selected arms + updated buffers (Alg. 1 lines 14-18).

    Order of operations follows Algorithm 1: v is updated with the *current*
    gradient (line 14) before the reward is computed (line 16), and prev_grad
    is replaced after (line 18).

    ``t`` is the ATTRIBUTION round of the feedback, not necessarily the
    server's wall-clock round: under the async cohort engine a gradient
    observed at round t was computed against the snapshot (and arm pull) of
    round t-s, and the caller passes that snapshot round here. Both
    time-dependent coefficients — the ``1 - gamma^t`` cosine weight and the
    ``gamma/t`` delta weight — are then evaluated at the pull round, so a
    stale observation is scored exactly as it would have been had it arrived
    synchronously (the delayed-feedback correction; the v/prev_grad buffers
    still advance in arrival order, matching Alg. 1's per-arm recursion).

    The (M, K) buffers are touched only through row gather/scatter of the
    selected arms, so passing a ``row_ops`` pair (``repro.kernels.ops.RowOps``)
    lets the same math run against row-sharded buffers inside ``shard_map``
    (the sharded round engine row-shards v/prev_grad exactly like the global
    model). ``None`` keeps the resident-table fast path.

    ``row_mask`` marks rows whose feedback never arrived (checksum-rejected
    wire rows under the fault layer): their rewards are zeroed and their
    v/prev_grad buffer rows are scattered back *unchanged* — the arm's
    reward recursion is exactly as if it had not been pulled. ``None``
    (the default) compiles the historical program byte-for-byte.
    """
    t = jnp.asarray(t, jnp.float32)
    if row_ops is None:
        v_sel = state.v[indices]
        prev_sel = state.prev_grad[indices]
    else:
        v_sel = row_ops.gather(state.v, indices)
        prev_sel = row_ops.gather(state.prev_grad, indices)

    v_new = update_v(v_sel, grads, beta2)
    if row_ops is not None:
        # pin the EMA's fusion boundary (see kernels.ops.RowOps): the same
        # expression must compile identically whether a resident or a
        # shard-local scatter consumes it
        from jax.lax import optimization_barrier
        v_new = optimization_barrier(v_new)

    if mode == "geometric":
        w_cos = 1.0 - jnp.power(gamma, t)
    elif mode == "paper_literal":
        w_cos = 1.0 - gamma * t
    else:
        raise ValueError(f"unknown reward mode: {mode!r}")

    # Eq. 13 cosine term. Bias-corrected vhat = v/(1-beta2^t) differs from
    # v_new by a positive scalar, to which cosine similarity is invariant, so
    # we use v_new directly (cheaper, numerically safer).
    cos_term = w_cos * _cosine_sim(v_new, grads, axis=-1)
    delta_term = (gamma / t) * jnp.sum(jnp.abs(prev_sel - grads), axis=-1)
    rewards = cos_term + delta_term

    if row_mask is not None:
        keep = row_mask[:, None]
        rewards = jnp.where(row_mask, rewards, 0.0)
        v_new = jnp.where(keep, v_new, v_sel)
        grads = jnp.where(keep, grads, prev_sel)

    if row_ops is None:
        new_state = RewardState(
            v=state.v.at[indices].set(v_new),
            prev_grad=state.prev_grad.at[indices].set(grads),
        )
    else:
        new_state = RewardState(
            v=row_ops.scatter_set(state.v, indices, v_new),
            prev_grad=row_ops.scatter_set(state.prev_grad, indices, grads),
        )
    return rewards, new_state
