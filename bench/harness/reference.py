"""Plain references for what the timed paths produce.

Written from the paper (Khan et al., RecSys 2021, Alg. 1 and Eqs. 3-14) and
the configuration files, in straightforward ``jax.numpy``. Nothing here
imports the program or takes anything it made: the reference builds its
own initial model, cohorts, selection stream and int8 wire images from the
seed, with the same conventions the configuration states:

  * Q0 = init_scale * N(0, 1) drawn with the first of three keys split from
    ``PRNGKey(seed)``; the selection stream starts at ``PRNGKey(seed + 13)``
    and is split once per round; round t's cohort is the t-th draw of
    ``numpy.random.default_rng(seed + 31).choice(users, theta,
    replace=False)``.
  * int8 wire: per-row symmetric, ``scale = rowmax|x| * (1/127)``, codes
    ``round(x * (1/scale))`` clipped to [-127, 127], decode ``code * scale``.

``dtype`` sets the precision the whole reference computes in: float32 at
``HIGHEST`` matmul precision is the reference; bfloat16 is the control.
``fault`` plants one of the faults the comparison must catch.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

FAULTS = (None, "half_cohort", "unchanged")


class RefRound(NamedTuple):
    """Static round hyper-parameters, read from the configuration."""

    num_items: int
    num_select: int
    theta: int
    k: int
    strategy: str            # "bts" | "full"
    l2: float
    alpha: float
    lr: float
    beta1: float
    beta2: float
    eps: float
    gamma: float
    mu_theta: float
    tau_theta: float
    init_scale: float


def ref_round_config(model: dict, mix: dict, num_items: int) -> RefRound:
    tr = mix["training"]
    strategy = tr["strategy"]
    m_s = num_items if strategy == "full" else \
        max(1, int(round(tr["keep_fraction"] * num_items)))
    opt = model["server_adam"]
    bandit = model["bandit"]
    return RefRound(
        num_items=num_items, num_select=m_s, theta=model["theta"],
        k=model["num_factors"], strategy=strategy, l2=model["l2"],
        alpha=model["alpha"], lr=opt["lr"], beta1=opt["beta1"],
        beta2=opt["beta2"], eps=opt["eps"], gamma=bandit["gamma"],
        mu_theta=bandit["mu_theta"], tau_theta=bandit["tau_theta"],
        init_scale=model["init_scale"])


def wire(x: jax.Array) -> jax.Array:
    """What the receiver decodes from x sent as int8 codes."""
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = absmax * (1.0 / 127.0)
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    codes = jnp.clip(jnp.round(x * inv), -127.0, 127.0)
    return (codes * scale).astype(x.dtype)


def cohorts(seed: int, rounds: int, num_users: int, theta: int) -> np.ndarray:
    rng = np.random.default_rng(seed + 31)
    return np.stack([rng.choice(num_users, size=min(theta, num_users),
                                replace=False)
                     for _ in range(rounds)]).astype(np.int32)


def init_state(cfg: RefRound, seed: int, dtype) -> Dict[str, jax.Array]:
    k_init = jax.random.split(jax.random.PRNGKey(seed), 3)[0]
    q0 = cfg.init_scale * jax.random.normal(
        k_init, (cfg.num_items, cfg.k), jnp.float32)
    z = jnp.zeros((cfg.num_items, cfg.k), dtype)
    zm = jnp.zeros((cfg.num_items,), dtype)
    return {"q": q0.astype(dtype), "m": z, "v": z,
            "t_rows": jnp.zeros((cfg.num_items,), jnp.int32),
            "key": jax.random.PRNGKey(seed + 13),
            "t": jnp.zeros((), jnp.int32),
            "reward_sum": zm, "counts": zm, "reward_v": z, "prev_grad": z}


def _select(cfg: RefRound, s: dict, k_sel: jax.Array, dtype) -> jax.Array:
    if cfg.strategy == "full":
        return jnp.arange(cfg.num_items, dtype=jnp.int32)
    n = s["counts"]
    z = jnp.where(n > 0, s["reward_sum"] / jnp.maximum(n, 1.0), 0.0)
    mu = (cfg.tau_theta * cfg.mu_theta + n * z) / (cfg.tau_theta + n)
    tau = cfg.tau_theta + n
    sample = mu + jax.lax.rsqrt(tau) * jax.random.normal(
        k_sel, (cfg.num_items,), dtype)
    _, idx = jax.lax.top_k(sample, cfg.num_select)
    return jnp.sort(idx).astype(jnp.int32)


def cohort_block(train, cohort: jax.Array, idx: jax.Array, num_items: int,
                 cap: Optional[int], dtype) -> jax.Array:
    """The cohort's (B, M_s) block of the interaction matrix: from the dense
    matrix, or from the lists ``(indptr, indices)``, whose B users hold at
    most ``cap`` ids together, by scattering ones into (B, M) and taking the
    selected columns."""
    if cap is None:
        return train[cohort][:, idx].astype(dtype)
    indptr, indices = train
    start = indptr[cohort]
    count = indptr[cohort + 1] - start
    end = jnp.cumsum(count)
    j = jnp.arange(cap, dtype=jnp.int32)
    row = jnp.minimum(jnp.searchsorted(end, j, side="right"),
                      cohort.shape[0] - 1)
    used = j < end[-1]
    at = jnp.where(used, start[row] + j - (end[row] - count[row]), 0)
    item = jnp.where(used, indices[at], num_items)          # dropped
    x = jnp.zeros((cohort.shape[0], num_items), dtype)
    return x.at[row, item].set(1, mode="drop")[:, idx]


def round_step(cfg: RefRound, train, s: dict, cohort: jax.Array, dtype,
               fault: Optional[str], cap: Optional[int] = None) -> dict:
    """One FCF round (Alg. 1 lines 8-18) against the train interactions
    (see :func:`cohort_block`)."""
    if fault == "unchanged":
        return s
    key, k_sel = jax.random.split(s["key"])
    t = s["t"] + 1
    idx = _select(cfg, s, k_sel, dtype)
    q_star = wire(s["q"][idx])                                  # downlink
    x = cohort_block(train, cohort, idx, cfg.num_items, cap, dtype)
    n_users = cohort.shape[0]
    data_scale = 1.0
    if fault == "half_cohort":
        keep = (jnp.arange(n_users) < n_users // 2).astype(dtype)
        x = x * keep[:, None]
        data_scale = n_users / (n_users // 2)
    c = 1.0 + cfg.alpha * x                                     # confidence
    eye = jnp.eye(cfg.k, dtype=dtype)
    # Eq. 3 per user: (Q* C_i Q*^T + l2 I) p_i = Q* C_i x_i
    lhs = jnp.einsum("bm,mk,ml->bkl", c, q_star, q_star) + cfg.l2 * eye
    rhs = jnp.einsum("bm,mk->bk", c * x, q_star)
    p = jnp.linalg.solve(lhs.astype(jnp.float32),
                         rhs.astype(jnp.float32)[..., None])[..., 0]
    p = p.astype(dtype)
    # Eqs. 5-6 summed over the cohort
    err = x - p @ q_star.T
    data = -2.0 * ((c * err).T @ p)
    grad = data_scale * data + 2.0 * cfg.l2 * n_users * q_star
    g = wire(grad)                                              # uplink
    # sparse Adam on the selected rows, per-row bias correction
    tr = s["t_rows"][idx] + 1
    tf = tr.astype(dtype)[:, None]
    m = cfg.beta1 * s["m"][idx] + (1 - cfg.beta1) * g
    v = cfg.beta2 * s["v"][idx] + (1 - cfg.beta2) * g * g
    mhat = m / (1.0 - jnp.power(jnp.asarray(cfg.beta1, dtype), tf))
    vhat = v / (1.0 - jnp.power(jnp.asarray(cfg.beta2, dtype), tf))
    q_rows = s["q"][idx] - cfg.lr * mhat / (jnp.sqrt(vhat) + cfg.eps)
    out = dict(s, key=key, t=t, q=s["q"].at[idx].set(q_rows),
               m=s["m"].at[idx].set(m), v=s["v"].at[idx].set(v),
               t_rows=s["t_rows"].at[idx].set(tr))
    if cfg.strategy != "bts":
        return out
    # Eqs. 13-14 on the data term of the decoded gradient, standardized
    fb = g - 2.0 * cfg.l2 * n_users * q_star
    rv = cfg.beta2 * s["reward_v"][idx] + (1 - cfg.beta2) * fb * fb
    tt = t.astype(dtype)
    w_cos = 1.0 - jnp.power(jnp.asarray(cfg.gamma, dtype), tt)
    cos = jnp.sum(rv * fb, -1) / jnp.maximum(
        jnp.linalg.norm(rv, axis=-1) * jnp.linalg.norm(fb, axis=-1), 1e-12)
    delta = (cfg.gamma / tt) * jnp.sum(jnp.abs(s["prev_grad"][idx] - fb), -1)
    r = w_cos * cos + delta
    r = (r - jnp.mean(r)) / jnp.maximum(jnp.std(r), 1e-9)
    r = jnp.where(jnp.isfinite(r), r, 0.0)
    out.update(reward_sum=s["reward_sum"].at[idx].add(r),
               counts=s["counts"].at[idx].add(1.0),
               reward_v=s["reward_v"].at[idx].set(rv),
               prev_grad=s["prev_grad"].at[idx].set(fb))
    return out


@functools.partial(jax.jit, static_argnames=("cfg", "dtype", "fault", "cap"))
def _scan(cfg: RefRound, train, s0: dict, ch: jax.Array, dtype,
          fault: Optional[str], cap: Optional[int]) -> dict:
    def body(s, cohort):
        return round_step(cfg, train, s, cohort, dtype, fault, cap), None

    with jax.default_matmul_precision("highest"):
        return jax.lax.scan(body, s0, ch)[0]


def run_training(cfg: RefRound, train, seed: int, rounds: int,
                 dtype=jnp.float32, fault: Optional[str] = None
                 ) -> Dict[str, np.ndarray]:
    """The reference's state after ``rounds`` rounds from the seed, with its
    initial model under ``q0``. ``train`` is the dense (users, items) matrix
    or the CSR triple ``(indptr, indices, (users, items))``."""
    if fault not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}, got {fault!r}")
    cap = None
    if isinstance(train, tuple):
        indptr, indices, (num_users, _) = train
        if indptr[-1] >= 2 ** 31:
            raise ValueError("the lists hold more ids than int32 indexes")
        count = np.sort(np.diff(indptr))
        cap = max(1, int(count[len(count) - min(cfg.theta, num_users):]
                         .sum()))
        train = (jnp.asarray(indptr.astype(np.int32)), jnp.asarray(indices))
    else:
        num_users = train.shape[0]
    ch = jnp.asarray(cohorts(seed, rounds, num_users, cfg.theta))
    s0 = init_state(cfg, seed, dtype)
    q0 = np.asarray(s0["q"], np.float32)
    final = _scan(cfg, train, s0, ch, jnp.dtype(dtype), fault, cap)
    out = {k: np.asarray(v.astype(jnp.float32) if v.dtype == dtype else v)
           for k, v in final.items() if k != "key"}
    out["q0"] = q0
    return out


@functools.partial(jax.jit, static_argnames=("n",))
def topn(table: jax.Array, p: jax.Array, seen: jax.Array, n: int):
    """Reference top-N over unseen items: scores of the decoded int8 wire
    table at float32 ``HIGHEST``. Returns ``(scores (B, M), top ids (B,
    n))``; seen items score -inf."""
    q = wire(table)
    s = jnp.dot(p.astype(jnp.float32), q.T,
                precision=jax.lax.Precision.HIGHEST)
    s = jnp.where(seen > 0, -jnp.inf, s)
    _, ids = jax.lax.top_k(s, n)
    return s, ids
