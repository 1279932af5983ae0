"""Adam (Kingma & Ba, 2015) from scratch — no optax in this environment.

Two entry points:

  * ``adam_update``       — dense update over an arbitrary pytree (LLM training).
  * ``adam_update_rows``  — sparse row-subset update over a 2-D table: only the
    selected rows' parameters *and moments* advance, with per-row timestep
    bias correction. This is the server-side update of Algorithm 1 line 13 for
    payload-selected item-factor (or vocab-embedding) rows.
  * ``adam_update_rows_scattered`` — same update with row traffic routed
    through the payload gather/scatter Pallas kernels; used by the fused
    ``server_round_step`` so a compiled FL round never copies the full table.

Paper server hyper-parameters (Table 3): beta1=0.1, beta2=0.99, eta=0.01,
eps=1e-8.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class AdamConfig(NamedTuple):
    lr: float = 0.01
    beta1: float = 0.1
    beta2: float = 0.99
    eps: float = 1e-8


class AdamState(NamedTuple):
    m: Any         # first-moment pytree (or (M, K) table for row mode)
    v: Any         # second-moment pytree
    t: jax.Array   # scalar step count (dense) or (M,) per-row step counts


def adam_init(params: Any, per_row: bool = False,
              moment: Optional[Any] = None) -> AdamState:
    """Zero state for ``params``. ``per_row=True`` is the row-subset mode
    over a single (M, K) table (per-row timesteps); ``moment`` (a
    :class:`repro.optim.state_compress.MomentCodecConfig`) selects
    compressed moment storage for that table — ``None`` or the fp32
    default allocates exactly the historical fp32 state."""
    if per_row:
        if not (hasattr(params, "shape") and hasattr(params, "dtype")):
            raise TypeError(
                "adam_init(per_row=True) operates on a single (M, K) row "
                f"table, not a pytree; got {type(params).__name__}. Build "
                "one per-row AdamState per table, or use per_row=False for "
                "pytree parameters.")
        num_rows = params.shape[0]
        t = jnp.zeros((num_rows,), jnp.int32)
        if moment is not None:
            from repro.optim import state_compress as sc  # deferred: no cycle

            if sc.is_compressed(moment):
                dim = params.shape[1]
                return AdamState(
                    m=sc.moment_init(moment.m_dtype, num_rows, dim),
                    v=sc.moment_init(moment.v_dtype, num_rows, dim),
                    t=t)
        return AdamState(m=jnp.zeros_like(params), v=jnp.zeros_like(params),
                         t=t)
    if moment is not None:
        raise ValueError("compressed moment storage (moment=...) requires "
                         "per_row=True — dense pytree Adam stays fp32")
    zeros = jax.tree.map(jnp.zeros_like, params)
    return AdamState(m=zeros, v=zeros, t=jnp.zeros((), jnp.int32))


def adam_update(
    grads: Any, state: AdamState, params: Any, config: AdamConfig = AdamConfig()
) -> Tuple[Any, AdamState]:
    """Standard dense Adam over a pytree. Returns (new_params, new_state)."""
    t = state.t + 1
    tf = t.astype(jnp.float32)
    b1, b2 = config.beta1, config.beta2

    m = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1) * g, state.m, grads)
    v = jax.tree.map(lambda vv, g: b2 * vv + (1 - b2) * jnp.square(g), state.v, grads)
    mhat_scale = 1.0 / (1.0 - jnp.power(b1, tf))
    vhat_scale = 1.0 / (1.0 - jnp.power(b2, tf))

    def step(p, mm, vv):
        return p - config.lr * (mm * mhat_scale) / (
            jnp.sqrt(vv * vhat_scale) + config.eps)

    new_params = jax.tree.map(step, params, m, v)
    return new_params, AdamState(m=m, v=v, t=t)


def adam_update_rows(
    grad_rows: jax.Array,   # (M_s, K) aggregated gradient for selected rows
    indices: jax.Array,     # (M_s,) row ids
    state: AdamState,       # per-row state over the full (M, K) table
    table: jax.Array,       # (M, K) full parameter table
    config: AdamConfig = AdamConfig(),
) -> Tuple[jax.Array, AdamState]:
    """Sparse Adam: advance only the selected rows (payload-subset update).

    Per-row timesteps keep bias correction exact for rows that are selected
    at different frequencies — important under bandit selection where popular
    arms are updated far more often than tail arms.
    """
    b1, b2 = config.beta1, config.beta2
    t_rows = state.t[indices] + 1
    tf = t_rows.astype(jnp.float32)[:, None]

    m_rows = b1 * state.m[indices] + (1 - b1) * grad_rows
    v_rows = b2 * state.v[indices] + (1 - b2) * jnp.square(grad_rows)
    mhat = m_rows / (1.0 - jnp.power(b1, tf))
    vhat = v_rows / (1.0 - jnp.power(b2, tf))
    new_rows = table[indices] - config.lr * mhat / (jnp.sqrt(vhat) + config.eps)

    return (
        table.at[indices].set(new_rows),
        AdamState(
            m=state.m.at[indices].set(m_rows),
            v=state.v.at[indices].set(v_rows),
            t=state.t.at[indices].set(t_rows),
        ),
    )


def adam_update_rows_scattered(
    grad_rows: jax.Array,   # (M_s, K) aggregated gradient for selected rows
    indices: jax.Array,     # (M_s,) row ids
    state: AdamState,       # per-row state over the full (M, K) table
    table: jax.Array,       # (M, K) full parameter table
    config: AdamConfig = AdamConfig(),
    row_ops=None,           # optional kernels.ops.RowOps override
    row_weights: Optional[jax.Array] = None,   # (M_s,) staleness discounts
    row_mask: Optional[jax.Array] = None,      # (M_s,) bool commit gate
    moment: Optional[Any] = None,              # MomentCodecConfig (fp32=None)
    moment_key: Optional[jax.Array] = None,    # SR dither key (int8 moments)
) -> Tuple[jax.Array, AdamState]:
    """:func:`adam_update_rows` with all row traffic routed through the
    payload gather / scatter kernels (:mod:`repro.kernels.ops`).

    Semantically identical to the ``.at[idx]`` variant; on TPU the four
    (M, K) tables (params, m, v) never materialize an O(M*K) copy — only the
    selected (M_s, K) tiles move through VMEM, which is what makes the fused
    scan round step cheap at LLM-vocab scale. On CPU the ops layer dispatches
    to the jnp oracles, so the math is bit-identical across backends.

    ``row_ops`` swaps the row gather/scatter pair, letting the sharded round
    engine run this exact update against row-sharded params/moments inside
    ``shard_map`` (collective gathers, shard-local scatters). The (M,)
    per-row timestep vector is cheap and always stays resident/replicated.

    ``row_weights`` is the async engine's per-row staleness discount: each
    committed row's *step* is scaled by its weight (FedAsync-style
    ``q <- q - w(s) * eta * step``). The discount deliberately lands on the
    step, not the gradient: Adam's update is near-invariant to gradient
    scaling (m and v scale together), so damping the gradient would damp
    nothing. Moments and per-row timesteps advance undamped — they are
    statistics of the arriving gradients, and a stale gradient is still an
    observation. A weight of exactly 1.0 is a bitwise no-op (IEEE multiply
    by one), which is what makes the async engine's ``max_staleness=0``
    trajectory bit-identical to the synchronous scan.

    ``row_mask`` is the fault layer's per-row commit gate (repro.faults):
    a False row scatters back its *old* table/moment/timestep values — an
    exact no-op, as if the row's update never arrived — which is how
    checksum-rejected wire rows are kept out of the model. ``None`` (the
    default) compiles the exact program this function always built.

    ``moment`` (a :class:`repro.optim.state_compress.MomentCodecConfig`)
    selects compressed moment storage: the update decodes the selected
    rows' moments to fp32 tiles, runs this exact math, and re-encodes —
    fp32 moments of the full table are never materialized. ``None`` or
    the fp32 default takes the code path below UNTOUCHED (the frozen ==
    today contract). ``moment_key`` seeds the stochastic-rounding dither
    for int8 moment writes (required iff the config stochastically
    rounds an int8 moment).
    """
    from repro.kernels import ops  # deferred: keep optim importable standalone

    if moment is not None:
        from repro.optim import state_compress as sc  # deferred: no cycle

        if sc.is_compressed(moment):
            return sc.adam_update_rows_compressed(
                grad_rows, indices, state, table, config, moment,
                key=moment_key, row_ops=row_ops, row_weights=row_weights,
                row_mask=row_mask)
    if row_ops is None:
        row_ops = ops.default_row_ops()
    b1, b2 = config.beta1, config.beta2
    t_rows = state.t[indices] + 1            # (M_s,) 1-D: plain jnp indexing
    tf = t_rows.astype(jnp.float32)[:, None]

    m_old = row_ops.gather(state.m, indices)
    v_old = row_ops.gather(state.v, indices)
    m_rows = b1 * m_old + (1 - b1) * grad_rows
    v_rows = b2 * v_old + (1 - b2) * jnp.square(grad_rows)
    mhat = m_rows / (1.0 - jnp.power(b1, tf))
    vhat = v_rows / (1.0 - jnp.power(b2, tf))
    step = config.lr * mhat / (jnp.sqrt(vhat) + config.eps)
    if row_weights is not None:
        step = step * row_weights.astype(jnp.float32)[:, None]
    table_old = row_ops.gather(table, indices)
    new_rows = table_old - step
    if row_mask is not None:
        keep = row_mask[:, None]
        m_rows = jnp.where(keep, m_rows, m_old)
        v_rows = jnp.where(keep, v_rows, v_old)
        new_rows = jnp.where(keep, new_rows, table_old)
        t_rows = jnp.where(row_mask, t_rows, state.t[indices])
    # pin the update expressions' fusion boundary on the consumer side too:
    # sandwiched between the gather barriers (RowOps contract) and this one,
    # the moment/param math compiles identically no matter which scatter
    # flavor (resident vs shard-local) consumes it — the bit-parity contract
    # between the sharded and single-device round engines
    from jax.lax import optimization_barrier
    m_rows, v_rows, new_rows = optimization_barrier(
        (m_rows, v_rows, new_rows))

    return (
        row_ops.scatter_set(table, indices, new_rows),
        AdamState(
            m=row_ops.scatter_set(state.m, indices, m_rows),
            v=row_ops.scatter_set(state.v, indices, v_rows),
            t=state.t.at[indices].set(t_rows),
        ),
    )


def sgd_update(grads: Any, params: Any, lr: float) -> Any:
    """Plain SGD (Eq. 4 without Adam), kept for ablations."""
    return jax.tree.map(lambda p, g: p - lr * g, params, grads)
