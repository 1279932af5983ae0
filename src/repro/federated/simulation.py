"""Round-based federated simulation of FCF / FCF-BTS / FCF-Random (Sec. 6).

Functional-core round engine. Each FL iteration t (Alg. 1):
  1. server (bandit) selects the payload subset and publishes Q*,
  2. a cohort of Theta users is sampled (simulating the asynchronous
     arrival of exactly-Theta updates that triggers a global commit),
  3. each user solves its private p_i from (Q*, x_i) and returns the
     item gradients; the server only ever sees the cohort aggregate,
  4. server commits: scatter-based sparse Adam on the selected rows,
     reward + BTS posterior update.

The whole round is ONE pure function (:func:`repro.cf.server.server_round_step`)
and the training loop is compiled end-to-end:

  * ``backend="scan"`` (default): cohort indices for all rounds are
    pre-sampled, the loop runs as ``jax.lax.scan`` over the fused step in
    chunks of ``eval_every`` rounds, with evaluation between chunks
    ("periodic chunked evaluation"). One compile, zero per-round Python
    dispatch — the engine for thousand-round experiment grids.
  * ``backend="python"``: the same jitted step driven round-by-round from
    Python. Kept as the reference implementation for equivalence testing
    (same PRNG seed => bit-identical selections, Q trajectory and byte
    counters) and as the dispatch-overhead baseline for
    ``benchmarks/round_engine.py``.
  * ``backend="async"``: the staleness-bounded async cohort engine
    (:func:`repro.cf.server.server_round_step_async`) — every round
    publishes a fresh encoded snapshot into a bounded ring and commits a
    cohort that solved against a snapshot up to ``max_staleness`` rounds
    old (the paper's deployment model, where exactly-Theta updates arrive
    asynchronously and may lag the global model). The staleness schedule is
    pre-sampled like the cohorts, so the whole async trajectory is one
    ``lax.scan``; ``max_staleness=0`` is bit-identical to ``backend="scan"``
    at equal cohort blocking. Composes with the sharded engine: set
    ``mesh_shards`` to run the async rounds under ``shard_map`` (the ring
    and pending buffers replicate — payload-sized — while the (M, K)
    tables row-shard exactly as in ``backend="shard"``).

Every engine runs one round closure, :func:`_make_round_fn`, which picks the
synchronous or async step once and takes this round's staleness and fault
slice only where they apply. The compiled engines ("scan", "async",
"shard") share one chunk builder, :func:`_make_chunk_runner`: one
``lax.scan`` body whose inputs (cohorts, staleness schedule, fault slice,
telemetry carry) are each present only where they apply, wrapped in one
``shard_map`` when a mesh is asked for. The python reference loop jits the
same round closure, and both loops end a chunk with the same
eval/checkpoint/publish boundary.

Sweep entry points (:func:`run_seed_sweep`, :func:`run_strategy_sweep`)
vectorize the same round closure with ``jax.vmap`` over per-seed server
states, so a multi-rebuild experiment cell runs as a single compiled program.

Interactions come in one of two layouts. A dense (users, items) matrix is
laid out on the device as float32 and closed over by the round programs. A
CSR triple ``(indptr, indices, (users, items))`` of host arrays, each
user's item ids in ``indices[indptr[i]:indptr[i + 1]]``, is laid out as
:class:`UserLists` (int32 on the device, no (users, items) array anywhere)
and enters the compiled chunk as an argument; the scan engine takes it,
the other engines refuse it.

Evaluation (Sec. 6.2): every ``eval_every`` rounds, a fixed user sample
downloads the *full* global model (the paper's inference-time download),
solves p_i on train data and computes normalized P/R/F1/MAP@10 on the
held-out 20%; the reported trajectory applies the paper's trailing-10
smoothing at read-out time.
"""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cf.metrics import RecMetrics, evaluate_users
from repro.cf.model import CFConfig, cf_init
from repro.cf.server import (
    FCFServerConfig, ServerState, ShardContext, server_init,
    server_round_step, server_round_step_async,
)
from repro.checkpoint.io import (
    checkpoint_step, latest_verified_checkpoint, load_checkpoint,
    save_checkpoint,
)
from repro.compress import (
    CodecConfig, direction_configs, validate_config, wire_bytes,
)
from repro.faults import (
    FaultConfig, FaultSchedule, SimulatedCrash, build_fault_schedule,
    fault_state_init, round_faults_xs,
)
from repro.core.selector import (
    STRATEGIES, SelectorConfig, selector_counts,
)
from repro.kernels.ops import fit_block_m
from repro.obs.config import ObsConfig
from repro.obs.telemetry import (
    make_row_emitter, telemetry_round, telemetry_state_init,
)
from repro.obs.trace import install_tracer, recording, span
from repro.optim.adam import AdamConfig
from repro.optim.state_compress import (
    MomentCodecConfig, validate_config as validate_moment_config,
)
from repro.utils.logging import MetricLogger, get_logger

log = get_logger("repro.fl")

BACKENDS = ("scan", "python", "shard", "async")
STALENESS_MODES = ("uniform", "max")


@dataclass
class FLSimConfig:
    strategy: str = "bts"            # bts | random | full | magnitude
    keep_fraction: float = 0.1       # payload kept per round (0.1 = 90% cut)
    rounds: int = 1000
    theta: int = 100                 # users per global commit (paper Sec. 6.1)
    num_factors: int = 25
    l2: float = 1.0
    alpha: float = 4.0
    lr: float = 0.01
    beta1: float = 0.1
    beta2: float = 0.99
    gamma: float = 0.999
    mu_theta: float = 0.0
    tau_theta: float = 10_000.0
    reward_mode: str = "geometric"
    reward_feedback: str = "data_term"   # "raw" = paper-literal feedback
    reward_norm: bool = True             # per-round reward standardization
    # payload wire format (repro.compress): fp32 | fp16 | int8 | int4 | topk
    codec: str = "fp32"
    # optimizer-state storage (repro.optim.state_compress): how Adam's
    # per-row moments live in server memory. fp32/fp32 (the default) is the
    # frozen path — programs bit-identical to every historical run. Other
    # choices (m: fp32|bf16|int8; v: fp32|bf16|int8|factored) shrink the
    # resident optimizer state (benchmarks/optimizer_state.py).
    moment_m_dtype: str = "fp32"
    moment_v_dtype: str = "fp32"
    # int8 moment writes round stochastically (unbiased) when True
    moment_stochastic_rounding: bool = True
    codec_topk_fraction: float = 0.25    # topk: fraction of dim kept per row
    codec_error_feedback: bool = True    # topk: carry the EF residual
    codec_int4_error_feedback: bool = False  # int4: carry the EF residual
    eval_every: int = 25
    eval_users: int = 512
    # evaluate the eval cohort in user-chunks of this size (None = one shot);
    # bounds the (B, M) score matrix at web-scale M
    eval_user_chunk: Optional[int] = None
    # item-block size for the fused chunked scorer during periodic eval
    # (kernels.wire_topn — no (B, M) score matrix). None = auto: whenever
    # eval_user_chunk is set, engage at the largest block whose scoring tile
    # fits VMEM for that chunk and K (kernels.ops.fit_block_m), else keep
    # the one-shot dense path. Bit-identical either way (test_serving.py).
    eval_item_chunk: Optional[int] = None
    # "scan" (default engine) | "python" (reference) | "shard" (shard_map
    # data-parallel rounds over a ("data",) device mesh) | "async"
    # (staleness-bounded async cohort queue; composes with mesh_shards)
    backend: str = "scan"
    # backend="async": a commit may land on a snapshot up to this many
    # rounds stale (ring depth = max_staleness + 1); 0 = synchronous
    max_staleness: int = 0
    # backend="async": client-phase block count per commit (the async
    # engine's cohort blocking — max_staleness=0 with blocks_per_commit=B is
    # bit-identical to backend="scan" with cohort_shards=B). Under
    # mesh_shards=D the mesh dictates one block per device: any other
    # explicit value is rejected at build time.
    blocks_per_commit: int = 1
    # backend="async": per-round staleness draw. "uniform" samples
    # s ~ U{0..max_staleness} (independent reporting lags); "max" pins
    # s = max_staleness — the saturation regime where the queue is always
    # full and every commit is maximally stale. Both clamp s <= t-1.
    staleness_mode: str = "uniform"
    # backend="async": Adam step discount**s for an s-stale commit
    staleness_discount: float = 0.8
    # client-phase block count: the cohort solve runs in this many equal user
    # blocks whose partial gradients are reduced in fixed order (see
    # server_round_step). The round's float semantics depend on this number
    # ONLY — backend="shard" over D devices is bit-identical to
    # backend="scan" with cohort_shards=D.
    cohort_shards: int = 1
    # backend="shard": devices on the "data" mesh axis (None = all local
    # devices). Overrides cohort_shards (one cohort block per device).
    mesh_shards: Optional[int] = None
    record_selections: bool = False      # surface per-round indices/rewards
    # serving publish hook, called at every eval boundary with
    # (round, server_state). repro.serve.ServingEngine.publisher() returns
    # one that installs the state's freshest encoded ring snapshot
    # (backend="async") — or an encoded full table otherwise — as the live
    # serving model without ever round-tripping through a dense fp32 Q.
    snapshot_hook: Optional[Callable[[int, ServerState], None]] = None
    # observability (repro.obs.ObsConfig): in-loop round telemetry streamed
    # through a batched io_callback, host span tracing, optional profiler
    # hook. None or enabled=False adds ZERO ops — trajectories stay
    # bit-identical (tests/test_obs.py). Single-run engines only; the
    # vmapped sweeps reject an enabled config.
    obs: Optional[ObsConfig] = None
    # fault injection (repro.faults.FaultConfig): deterministic pre-sampled
    # client dropout / straggler timeouts / wire-row corruption / simulated
    # host crash, threaded through the compiled engines as scan xs. None or
    # enabled=False adds ZERO ops — trajectories stay bit-identical
    # (tests/test_faults.py). Single-run engines only; mutually exclusive
    # with an enabled obs config (both re-plumb the same scan programs).
    faults: Optional[FaultConfig] = None
    # round-checkpoint directory: at every eval boundary the full ServerState
    # is written with atomic temp+rename and a sha256 sidecar
    # (repro.checkpoint.io). None disables checkpointing.
    checkpoint_dir: Optional[str] = None
    # crash-resume: a checkpoint FILE to resume from, or a DIRECTORY whose
    # newest hash-verified checkpoint is used. Training skips every round
    # the checkpoint already committed; because cohorts, staleness and
    # faults are pre-sampled schedules, the resumed trajectory is
    # bit-identical to an uninterrupted run (tests/test_faults.py). A
    # resumed config should clear faults.crash_round (or the run re-crashes
    # at the same round).
    resume_from: Optional[str] = None
    seed: int = 0


@dataclass
class SimResult:
    final: Dict[str, float]
    history: MetricLogger
    bytes_down: int
    bytes_up: int
    rounds: int
    selection_counts: np.ndarray
    # per-round (rounds, M_s) selected indices / rewards, populated only
    # when config.record_selections (equivalence tests, selection audits)
    selections: Optional[np.ndarray] = None
    rewards: Optional[np.ndarray] = None
    # the raw final server pytree (traced byte counters included)
    server_state: Optional[ServerState] = field(default=None, repr=False)
    # snapshot_hook invocations that raised (training continues; a serving
    # publish failure must never abort the round loop)
    hook_failures: int = 0

    def smoothed(self, key: str, window: int = 10) -> float:
        return self.history.rolling_mean(key, window)


# ===================================================================== #
# setup
# ===================================================================== #
class _SimSetup(NamedTuple):
    cf_cfg: CFConfig
    sel_cfg: SelectorConfig
    srv_cfg: FCFServerConfig
    codec_cfg: CodecConfig
    state0: ServerState
    cohorts: np.ndarray        # (rounds, B) int32 pre-sampled cohort ids
    staleness: np.ndarray      # (rounds,) int32 pre-sampled snapshot ages
    eval_train: jax.Array      # (E, M)
    eval_test: jax.Array       # (E, M)
    # pre-sampled fault schedule (repro.faults), None when faults are off
    fault_sched: Optional[FaultSchedule] = None


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["indptr", "indices"],
                   meta_fields=["num_items", "piece"])
@dataclass(frozen=True)
class UserLists:
    """Each user's item ids on the device: user i holds
    ``indices.reshape(-1)[indptr[i]:indptr[i + 1]]``. The ids are laid out
    in rows of ``chunk`` (zero-padded at the end), so the cohort gather reads
    them a whole row at a time. A pytree of the two int32 arrays, so it
    enters a compiled program as an argument; ``num_items`` and ``piece``
    (the id slots the cohort gather walks per step) are static."""
    indptr: jax.Array          # (N + 1,) int32
    indices: jax.Array         # (ceil(nnz / chunk), chunk) int32
    num_items: int
    piece: int

    @property
    def chunk(self) -> int:
        return self.indices.shape[1]


def _is_lists(x) -> bool:
    """Whether ``x`` is a CSR triple: a 3-tuple whose last item is a 2-tuple
    of ints, ``(indptr, indices, (users, items))``."""
    if not (isinstance(x, tuple) and len(x) == 3):
        return False
    shape = x[2]
    return (isinstance(shape, tuple) and len(shape) == 2
            and all(isinstance(d, (int, np.integer)) for d in shape))


def _checked_lists(x, name: str):
    """``(indptr int64, indices int32, (users, items))`` on the host, or a
    ValueError that names what is wrong with the triple."""
    if not _is_lists(x):
        raise ValueError(
            f"{name} must be a CSR triple (indptr, indices, (users, items)) "
            f"like the other split")
    indptr, indices, (n, m) = x
    indptr, indices = np.asarray(indptr), np.asarray(indices)
    if indptr.ndim != 1 or indptr.shape[0] != n + 1 \
            or not np.issubdtype(indptr.dtype, np.integer):
        raise ValueError(
            f"{name}: indptr must be {n + 1} integers (users + 1), got "
            f"{indptr.dtype} {indptr.shape}")
    if indices.ndim != 1 or not np.issubdtype(indices.dtype, np.integer):
        raise ValueError(f"{name}: indices must be a 1-D integer array, got "
                         f"{indices.dtype} {indices.shape}")
    if indices.shape[0] >= 2 ** 31:
        raise ValueError(f"{name}: {indices.shape[0]} ids do not fit int32 "
                         f"offsets (2**31 or more)")
    if indptr[0] != 0 or indptr[-1] != indices.shape[0]:
        raise ValueError(
            f"{name}: indptr must run from 0 to len(indices) = "
            f"{indices.shape[0]}, got {indptr[0]} .. {indptr[-1]}")
    if np.any(np.diff(indptr) < 0):
        raise ValueError(f"{name}: indptr is not monotone")
    if indices.shape[0] and (indices.min() < 0 or indices.max() >= m):
        raise ValueError(
            f"{name}: item ids must lie in [0, {m}), got {indices.min()} .. "
            f"{indices.max()}")
    return (indptr.astype(np.int64, copy=False),
            indices.astype(np.int32, copy=False), (int(n), int(m)))


def _list_piece(theta: int, num_users: int, num_ids: int) -> int:
    """Id slots the cohort gather walks per step: the power of two at or
    above an eighth of an average cohort's ids (at least 128). It depends on
    the data and Theta only, never on the seed, so a new seed compiles
    nothing; the walk pads a round by less than one piece."""
    mean = theta * num_ids / max(num_users, 1)
    return 1 << max(7, int(np.ceil(np.log2(max(mean / 8.0, 1.0)))))


def _list_chunk(degrees: np.ndarray) -> int:
    """Ids in one row of the laid-out lists, which the cohort gather reads
    whole: the power of two at or below a quarter of the median degree, at
    least 8. Set by the data alone. A list of d ids touches about ``d /
    chunk + 1`` rows, so short lists get short rows."""
    quarter = float(np.median(degrees)) / 4.0 if len(degrees) else 0.0
    return 1 << max(3, int(np.floor(np.log2(max(quarter, 1.0)))))


def _rows_touched(start, stop, chunk: int):
    """Rows of ``chunk`` ids that each list ``[start, stop)`` of the flat
    ids touches (NumPy or JAX arrays)."""
    return (stop > start) * ((stop - 1) // chunk - start // chunk + 1)


def _id_rows(indices: np.ndarray, chunk: int) -> np.ndarray:
    """The flat ids as (ceil(nnz / chunk), chunk) rows, zero-padded (one row
    at least)."""
    rows = max(1, -(-indices.shape[0] // chunk))
    out = np.zeros(rows * chunk, np.int32)
    out[:indices.shape[0]] = indices
    return out.reshape(rows, chunk)


def _dense_rows(lists, ids: np.ndarray) -> np.ndarray:
    """The users ``ids``' rows of the (users, items) matrix, float32, built
    on the host from the CSR triple."""
    indptr, indices, (_, m) = lists
    start, count = indptr[ids], indptr[ids + 1] - indptr[ids]
    out = np.zeros((len(ids), m), np.float32)
    row = np.repeat(np.arange(len(ids)), count)
    pos = np.repeat(start - (np.cumsum(count) - count), count) \
        + np.arange(int(count.sum()))
    out[row, indices[pos]] = 1.0
    return out


def _eval_rows(x, ids: jax.Array) -> jax.Array:
    """The eval users' (E, M) rows: indexed from the dense matrix, or built
    once from the lists."""
    if _is_lists(x):
        return jnp.asarray(_dense_rows(x, np.asarray(ids)))
    return x[ids]


def _lay_out_lists(train_x, test_x, config: FLSimConfig):
    """Validate both CSR triples, put the train lists on the device under
    the span ``lists.layout``, and build the set-up. Returns ``(UserLists,
    _SimSetup)``; the eval users' rows are the only dense rows."""
    if config.backend != "scan":
        raise ValueError(
            f"per-user item lists (a CSR triple) run on backend='scan' only; "
            f"backend={config.backend!r} takes the dense (users, items) "
            f"matrix")
    train_l = _checked_lists(train_x, "train_x")
    test_l = _checked_lists(test_x, "test_x")
    if train_l[2] != test_l[2]:
        raise ValueError(f"train_x is {train_l[2]} and test_x {test_l[2]}: "
                         f"the splits must have one (users, items) shape")
    indptr, indices, (n, m) = train_l
    theta = min(config.theta, n)
    degrees = np.diff(indptr)
    chunk = _list_chunk(degrees)
    rows = _rows_touched(indptr[:-1], indptr[1:], chunk)
    cap = int(np.sort(degrees)[n - theta:].sum())
    cap_chunks = int(np.sort(rows)[n - theta:].sum())
    with span("lists.layout", users=n, ids=int(indices.shape[0]), cap=cap,
              chunk=chunk, cap_chunks=cap_chunks):
        lists = UserLists(
            indptr=jnp.asarray(indptr.astype(np.int32)),
            indices=jnp.asarray(_id_rows(indices, chunk)), num_items=m,
            piece=_list_piece(theta, n, int(indices.shape[0])))
        jax.block_until_ready(lists)
    return lists, _build(train_l, test_l, config)


def _num_select(config: FLSimConfig, num_items: int) -> int:
    if config.strategy == "full":
        return num_items
    return max(1, int(round(config.keep_fraction * num_items)))


def _chunk_bounds(rounds: int, eval_every: int) -> List[Tuple[int, int]]:
    """[(start, end)] chunks whose right edges are the evaluation rounds."""
    points = sorted({t for t in range(eval_every, rounds + 1, eval_every)}
                    | {rounds})
    bounds, start = [], 0
    for p in points:
        bounds.append((start, p))
        start = p
    return bounds


def _build(train_j, test_j, config: FLSimConfig) -> _SimSetup:
    """Pure-data setup shared by every backend: states, cohorts, eval split.

    ``train_j``/``test_j`` are the dense (N, M) matrices or checked CSR
    triples (:func:`_checked_lists`); only the eval users' rows are dense.

    PRNG discipline matches the legacy stateful path: PRNGKey(seed) splits
    into (init, users, eval); the selection stream is PRNGKey(seed+13) split
    once per round; cohorts come from numpy default_rng(seed+31); the async
    staleness schedule from default_rng(seed+47).
    """
    if config.strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {config.strategy!r}")
    if config.backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, "
                         f"got {config.backend!r}")
    is_async = config.backend == "async"
    if config.max_staleness < 0:
        raise ValueError(
            f"max_staleness must be >= 0, got {config.max_staleness}")
    if config.max_staleness > 0 and not is_async:
        raise ValueError(
            "max_staleness > 0 requires backend='async' (the synchronous "
            "backends commit the snapshot they just published)")
    if is_async and config.staleness_mode not in STALENESS_MODES:
        raise ValueError(
            f"staleness_mode must be one of {STALENESS_MODES}, "
            f"got {config.staleness_mode!r}")
    if is_async and config.blocks_per_commit < 1:
        raise ValueError(
            f"blocks_per_commit must be >= 1, got {config.blocks_per_commit}")
    if config.obs is not None:
        config.obs.validate()
    fault_cfg = config.faults
    fault_on = fault_cfg is not None and fault_cfg.enabled
    if fault_cfg is not None:
        fault_cfg.validate()
    if fault_on and config.obs is not None and config.obs.enabled:
        raise ValueError(
            "config.faults and config.obs cannot both be enabled: both "
            "re-plumb the compiled round scans, and their composition is "
            "untested — run the faulted trajectory without telemetry")
    if is_async and config.mesh_shards is not None \
            and config.blocks_per_commit not in (1, config.mesh_shards):
        raise ValueError(
            f"backend='async' with mesh_shards={config.mesh_shards} runs "
            f"one cohort block per device; blocks_per_commit="
            f"{config.blocks_per_commit} conflicts (leave it at 1 or set "
            f"it equal to mesh_shards)")
    num_users, num_items = train_j[2] if _is_lists(train_j) \
        else train_j.shape
    key = jax.random.PRNGKey(config.seed)
    k_init, _k_users, k_eval = jax.random.split(key, 3)

    cf_cfg = CFConfig(
        num_users=num_users, num_items=num_items,
        num_factors=config.num_factors, l2=config.l2, alpha=config.alpha,
    )
    sel_cfg = SelectorConfig(
        strategy=config.strategy, num_arms=num_items,
        num_select=_num_select(config, num_items), dim=config.num_factors,
        gamma=config.gamma, beta2=config.beta2, mu_theta=config.mu_theta,
        tau_theta=config.tau_theta, reward_mode=config.reward_mode,
        reward_norm=config.reward_norm,
    )
    moment_cfg = None
    if (config.moment_m_dtype, config.moment_v_dtype) != ("fp32", "fp32"):
        moment_cfg = MomentCodecConfig(
            m_dtype=config.moment_m_dtype, v_dtype=config.moment_v_dtype,
            stochastic_rounding=config.moment_stochastic_rounding)
        validate_moment_config(moment_cfg)
    srv_cfg = FCFServerConfig(
        theta=config.theta,
        adam=AdamConfig(lr=config.lr, beta1=config.beta1,
                        beta2=config.beta2, eps=1e-8),
        reward_feedback=config.reward_feedback, l2=config.l2,
        staleness_discount=config.staleness_discount,
        moment=moment_cfg,
    )
    codec_cfg = CodecConfig(
        name=config.codec, topk_fraction=config.codec_topk_fraction,
        error_feedback=config.codec_error_feedback,
        int4_error_feedback=config.codec_int4_error_feedback,
    )
    validate_config(codec_cfg)
    model = cf_init(cf_cfg, k_init)
    state0 = server_init(
        model.item_factors, sel_cfg,
        key=jax.random.PRNGKey(config.seed + 13),
        config=srv_cfg, codec_cfg=codec_cfg,
        async_slots=(config.max_staleness + 1) if is_async else None,
        force_residual=fault_on and fault_cfg.corrupt_rate > 0.0)
    if fault_on:
        state0 = state0._replace(faults=fault_state_init())

    cohort_n = min(config.theta, num_users)
    rng = np.random.default_rng(config.seed + 31)
    cohorts = np.stack([
        rng.choice(num_users, size=cohort_n, replace=False)
        for _ in range(config.rounds)
    ]).astype(np.int32)
    staleness = _staleness_schedule(config)
    fault_sched = None
    if fault_on:
        fault_sched = build_fault_schedule(
            fault_cfg, config.rounds, cohort_n, sel_cfg.num_select,
            config.seed)

    eval_n = min(config.eval_users, num_users)
    eval_ids = jax.random.choice(k_eval, num_users, (eval_n,), replace=False)
    return _SimSetup(
        cf_cfg=cf_cfg, sel_cfg=sel_cfg, srv_cfg=srv_cfg,
        codec_cfg=codec_cfg, state0=state0,
        cohorts=cohorts, staleness=staleness,
        eval_train=_eval_rows(train_j, eval_ids),
        eval_test=_eval_rows(test_j, eval_ids),
        fault_sched=fault_sched,
    )


def _staleness_schedule(config: FLSimConfig) -> np.ndarray:
    """Pre-sampled per-round snapshot ages for the async engine.

    Round t's commit lands on the snapshot published at round t - s_t. The
    schedule is data, exactly like the cohort schedule: "uniform" draws
    independent reporting lags s ~ U{0..S}, "max" pins every commit at the
    staleness bound (queue saturated). Either way s_t <= t-1, so the first
    rounds never reference snapshots that do not exist yet. All-zero for the
    synchronous backends (and for max_staleness=0, where the async engine
    reduces to the scan engine bit-for-bit).
    """
    rounds, s_max = config.rounds, config.max_staleness
    if config.backend != "async" or s_max == 0:
        return np.zeros((rounds,), np.int32)
    if config.staleness_mode == "max":
        s = np.full((rounds,), s_max, np.int64)
    else:
        rng = np.random.default_rng(config.seed + 47)
        s = rng.integers(0, s_max + 1, size=rounds)
    return np.minimum(s, np.arange(rounds)).astype(np.int32)


def _cohort_block(train, ids: jax.Array, idx: jax.Array) -> jax.Array:
    """``train[ids][:, idx]``: the cohort's (len(ids), len(idx)) block.

    Gathered in two stages of contiguous slices: the cohort's user rows
    whole, (B, M), then the payload columns as rows of that slab's
    transpose, (M_s, B). The one-step ``train[ids[:, None], idx[None, :]]``
    lowers to B x M_s single-element slices, which cost per element on the
    TPU. Both are exact, so the block is bit-equal either way. From
    :class:`UserLists` it is :func:`_cohort_block_lists`, bit-equal too.
    """
    if isinstance(train, UserLists):
        return _cohort_block_lists(train, ids, idx)
    return train[ids].T[idx].T


def _cohort_block_lists(lists: UserLists, ids: jax.Array,
                        idx: jax.Array) -> jax.Array:
    """The cohort's (B, M_s) block from per-user item lists, as 0/1 float32.

    Each selected item maps to its column through an (M,) int32 map (M_s,
    out of range, elsewhere). A user's ids span whole rows of
    ``lists.indices``; the cohort's rows, user after user, are walked
    ``lists.piece // lists.chunk`` at a time in a ``while_loop`` bounded by
    their true count: each row finds its user by its place in the walk and
    is gathered whole, and each of its ids finds its column by the map and
    sets a one straight into the block. Ids of the row's other users, of
    unselected items, and the walk's padding are dropped. No shape depends
    on the cohort. The block is set flat, as its (M_s, B) transpose, the
    layout the dense path's column gather leaves, so the round's consumers
    compile alike and the state is bit-equal to the dense path's. It is set
    as int8, a quarter of float32's bytes, so the walk does not crowd the
    round's tables out of the TPU's VMEM; 0 and 1 convert exactly.
    """
    b, m_s, c = ids.shape[0], idx.shape[0], lists.chunk
    per_step = max(1, lists.piece // c)
    start = lists.indptr[ids]
    stop = lists.indptr[ids + 1]
    rows = _rows_touched(start, stop, c)
    end = jnp.cumsum(rows)                       # walk offset past user
    first = end - rows
    total = end[-1]
    col = jnp.full((lists.num_items,), m_s, jnp.int32).at[idx].set(
        jnp.arange(m_s, dtype=jnp.int32))
    lane = jnp.arange(c, dtype=jnp.int32)
    last_row = lists.indices.shape[0] - 1

    def walk(carry):
        k, flat = carry
        j = k * per_step + jnp.arange(per_step, dtype=jnp.int32)
        user = jnp.searchsorted(end, j, side="right",
                                method="compare_all").astype(jnp.int32)
        r = jnp.minimum(user, b - 1)                 # user == b: past the end
        g = jnp.minimum(start[r] // c + j - first[r], last_row)
        p = g[:, None] * c + lane                    # (P, C) flat positions
        used = (j < total)[:, None] & (p >= start[r][:, None]) \
            & (p < stop[r][:, None])
        cols = jnp.where(used, col[lists.indices[g]], m_s)
        at = jnp.where(cols < m_s, cols * b + r[:, None], m_s * b)
        return k + 1, flat.at[at].set(1, mode="drop")

    steps = (total + per_step - 1) // per_step
    _, flat = jax.lax.while_loop(
        lambda carry: carry[0] < steps, walk,
        (jnp.int32(0), jnp.zeros((m_s * b,), jnp.int8)))
    return flat.reshape(m_s, b).T.astype(jnp.float32)


def _cohort_x(data, ids: jax.Array, blocks: int, num_users: Optional[int],
              survivors: Optional[jax.Array] = None, offset=0):
    """Lazy blocked cohort slice for the round step, ``idx -> (blocks, b,
    M_s)``.

    ``ids`` are this caller's rows of the padded cohort, starting at row
    ``offset`` of it: the whole cohort on one device (``offset`` 0), one
    device's block under ``shard_map`` (``blocks`` 1, ``offset`` the traced
    ``axis_index * b``). Rows at or past ``num_users`` are padding and are
    zeroed — an all-zero x row solves to p=0 and contributes exactly zero to
    every aggregate, so padding never changes the math. ``num_users`` is
    None when the padded cohort has no padding rows, which traces no mask.

    ``survivors`` (the fault layer's (total,) f32 keep vector over the whole
    padded cohort) additionally zeroes dropped/straggling users' rows — the
    same exact-no-op mechanism as padding, composed multiplicatively with
    the static pad mask. ``None`` compiles the historical closure untouched.
    """
    rows = ids.shape[0]
    traced = not isinstance(offset, int)    # one block of a device mesh

    def cohort_x(idx):
        x = _cohort_block(data, ids, idx)                    # (rows, M_s)
        if num_users is not None:
            pos = jnp.arange(rows)
            if traced:
                pos = offset + pos
            x = x * (pos < num_users).astype(x.dtype)[:, None]
        if survivors is not None:
            keep = survivors
            if traced:
                keep = jax.lax.dynamic_slice_in_dim(survivors, offset, rows)
            x = x * keep.astype(x.dtype)[:, None]
        return x.reshape(blocks, rows // blocks, idx.shape[0])

    return cohort_x


def _padded_size(cohort_size: int, blocks: int) -> int:
    """The cohort's size padded up to ``blocks`` equal blocks."""
    return blocks * -(-cohort_size // blocks)


def _pad_cohort(cohort: jax.Array, shards: int) -> jax.Array:
    """Pad a flat (B,) cohort id vector to a multiple of ``shards``.

    Pad entries reuse user id 0; their interaction rows are masked to zero
    by :func:`_cohort_x` so they are exact no-ops.
    """
    b_total = cohort.shape[0]
    return jnp.pad(cohort, (0, _padded_size(b_total, shards) - b_total))


def _cohort_blocks(config: FLSimConfig) -> Tuple[int, bool]:
    """``(blocks, on_mesh)``: the number of equal blocks each round's cohort
    is solved in, on which the round's float semantics depend, and whether
    the rounds run under ``shard_map``. A mesh (``backend="shard"``, or
    ``"async"`` given ``mesh_shards``; plain async is single-device) solves
    one block per device; otherwise async takes ``blocks_per_commit`` and
    the other engines ``cohort_shards``."""
    if config.backend == "shard" or (config.backend == "async"
                                     and config.mesh_shards is not None):
        return config.mesh_shards or len(jax.devices()), True
    if config.backend == "async":
        return config.blocks_per_commit, False
    return config.cohort_shards, False


def _make_round_fn(data, setup: _SimSetup, *, blocks: int, is_async: bool,
                   telemetry: bool, shard: Optional[ShardContext] = None):
    """``(state, cohort, stale=None, rf=None) -> (state, RoundAux)``: one
    fused FL round over the cohort split into ``blocks`` equal blocks.

    ``is_async`` builds :func:`repro.cf.server.server_round_step_async`,
    which commits against the snapshot ``stale`` rounds old; otherwise
    :func:`repro.cf.server.server_round_step`. Under ``shard`` (inside
    ``shard_map`` over ``blocks`` devices) ``cohort`` is this device's
    (1, b) block of the padded cohort; on one device it is the flat (B,)
    cohort.

    ``rf``, this round's :class:`repro.faults.RoundFaults` slice, zeroes
    dropped/straggling users out of the cohort (exact no-op rows) and
    renormalizes the gradient over the traced survivor count. ``rf=None``
    traces byte-for-byte the program without the fault layer.
    """
    step = server_round_step_async if is_async else server_round_step
    n = setup.cohorts.shape[1]
    pad_users = n if _padded_size(n, blocks) > n else None

    def round_fn(state: ServerState, cohort: jax.Array, stale=None, rf=None):
        if shard is None:
            ids, offset, local = _pad_cohort(cohort, blocks), 0, blocks
        else:
            ids = cohort.reshape(-1)
            offset = jax.lax.axis_index(shard.axis) * ids.shape[0]
            local = 1
        cohort_x = _cohort_x(data, ids, local, pad_users,
                             None if rf is None else rf.survivors, offset)
        num_users = n if rf is None else jnp.sum(rf.survivors)
        args = (state, cohort_x) + ((stale,) if is_async else ())
        return step(*args, sel_cfg=setup.sel_cfg, config=setup.srv_cfg,
                    cf_cfg=setup.cf_cfg, codec_cfg=setup.codec_cfg,
                    num_users=num_users, shard=shard, telemetry=telemetry,
                    faults=rf)

    return round_fn


def _make_chunk_runner(train, setup: _SimSetup, config: FLSimConfig,
                       record: bool, obs: Optional[ObsConfig]):
    """Compile the round scan of ``backend`` "scan", "async" or "shard".

    Returns ``(run_chunk, state0)``. ``run_chunk(state, lo, hi)`` commits
    rounds ``[lo, hi)`` of the pre-sampled schedules as one compiled program
    and returns ``(state, ys)``, ``ys`` the rounds'
    :class:`repro.cf.server.RoundAux` when ``record`` (else None).

    One scan body serves every engine. The per-round inputs ride the scan as
    ``xs = (cohorts, stale, rf)``: the staleness schedule only for async, the
    :class:`repro.faults.RoundFaults` slice only with faults on. With ``obs``
    (an *enabled* :class:`ObsConfig`) the TelemetryState rides the carry and
    each round's packed (15,) row is a ys output. An absent input is None,
    which leaves no leaf in the pytree, so each combination's program is
    byte-for-byte the one it would be written alone: obs and faults off add
    zero ops, and the benchmark's chunk programs keep their hashes
    (``scripts/chunk_hlo.py``).

    Under a mesh one ``shard_map`` over the ("data",) axis wraps the same
    chunk. ``state0``'s (M, K) tables (Q, Adam moments, BTS reward buffers,
    codec residual) are row-sharded over the devices, everything else is
    replicated, and each device solves one block of ceil(B/D) users per
    round, so per round only payload-sized tensors cross the interconnect
    (encoded Q* candidates, partial gradients, selected-row gathers).
    Trajectories are bit-identical to ``backend="scan"`` with
    ``cohort_shards=D`` (see :func:`repro.cf.server.server_round_step`).
    Async's snapshot ring and pending-attribution buffers replicate with the
    selector posteriors (they are payload-sized): a stale block is just a
    block solved against an older Q*, so the collective schedule is exactly
    the synchronous one.

    Telemetry rows leave through one batched ``io_callback`` per chunk on
    one device. There can be no ``io_callback`` inside ``shard_map``, so
    under a mesh the rows come back replicated as an output and are emitted
    on the host after the chunk.
    """
    from jax.experimental import io_callback

    is_async = config.backend == "async"
    fault_on = config.faults is not None and config.faults.enabled
    blocks, on_mesh = _cohort_blocks(config)
    n = setup.cohorts.shape[1]
    pad_total = _padded_size(n, blocks)
    emitter = tel0 = shard = None
    if obs is not None:
        emitter = make_row_emitter(obs.resolve_sink(), obs.telemetry_every)
        tel0 = telemetry_state_init(setup.sel_cfg.num_arms)
    tel_holder = [tel0]
    state0 = setup.state0
    if on_mesh:
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_data_mesh
        from repro.launch.sharding import fcf_state_pspecs, to_shardings

        m = setup.cf_cfg.num_items
        if m % blocks:
            raise ValueError(
                f"backend='shard' row-shards the (M, K) tables: num_items={m} "
                f"must divide evenly over {blocks} devices")
        mesh = make_data_mesh(blocks)
        shard = ShardContext(axis="data", num_shards=blocks,
                             rows_per_shard=m // blocks)
        state_specs = fcf_state_pspecs(setup.state0)
        state0 = jax.device_put(setup.state0, to_shardings(mesh, state_specs))
    # per-user lists enter the chunk as its argument ``data_arg``, and so
    # does the dense matrix under a mesh (replicated); on one device the
    # dense matrix stays closed over and ``data_arg`` is None, so its
    # programs are as they were
    data = train if on_mesh or isinstance(train, UserLists) else None

    # the program's text holds this function's name (its HLO module is
    # ``jit_scan_chunk``, which the benchmark's trace readers and
    # ``scripts/chunk_hlo.py`` look for) and names its parameters after these
    # arguments, so both are part of each chunk program's hash
    def scan_chunk(st, tel, cohorts, stale, rf, data_arg):
        round_fn = _make_round_fn(
            train if data_arg is None else data_arg, setup, blocks=blocks,
            is_async=is_async, telemetry=tel is not None, shard=shard)

        def body(carry, x):
            s, ts = carry
            s, aux = round_fn(s, *x)
            row = None
            if ts is not None:
                ts, row = telemetry_round(
                    ts, aux.telemetry, aux.indices, aux.rewards)
            ys = aux._replace(telemetry=()) if record else None
            return (s, ts), (ys, row)

        (st, tel), (ys, rows) = jax.lax.scan(body, (st, tel),
                                             (cohorts, stale, rf))
        if rows is not None and shard is None:
            # one BATCHED host callback per compiled chunk; the host side
            # applies the telemetry_every rate limit
            io_callback(emitter, None, rows, ordered=True)
            rows = None
        return st, tel, ys, rows

    if shard is None:
        run = jax.jit(scan_chunk)
    else:
        # the blocked cohorts (R, D, b) split over the devices; telemetry,
        # staleness, the RoundFaults slice (survivors/corrupt are
        # payload-sized, and the replicated survivor sum renormalizes the
        # gradient identically on all shards) and the matrix replicate
        run = jax.jit(jax.shard_map(
            scan_chunk, mesh=mesh,
            in_specs=(state_specs, P(), P(None, "data", None), P(), P(),
                      P()),
            out_specs=(state_specs, P(), P(), P()), check_vma=False))

    def run_chunk(state, lo: int, hi: int):
        cohorts = setup.cohorts[lo:hi]
        if shard is not None:
            cohorts = np.pad(cohorts, ((0, 0), (0, pad_total - n))).reshape(
                hi - lo, blocks, -1)
        stale = rf = None
        if is_async:
            stale = jnp.asarray(setup.staleness[lo:hi], jnp.int32)
        if fault_on:
            rf = round_faults_xs(setup.fault_sched, lo, hi, pad_to=pad_total)
        state, tel_holder[0], ys, rows = run(
            state, tel_holder[0], jnp.asarray(cohorts), stale, rf, data)
        if rows is not None:
            emitter(np.asarray(rows))
        return state, ys

    return run_chunk, state0


def _evaluate(q: jax.Array, eval_train: jax.Array, eval_test: jax.Array,
              config: FLSimConfig) -> RecMetrics:
    """Full-model eval, optionally chunked over users (bounded memory).

    Chunk results combine exactly: each chunk mean is re-weighted by its
    count of valid (non-empty-test) users before averaging. When user
    chunking is on, scoring also reroutes through the fused chunked top-k
    scorer (``evaluate_users(item_chunk=...)``) so neither axis of the
    (B, M) score matrix is materialized — bit-identical to the dense path
    (same mask sentinel, same top_k tie order).
    """
    chunk = config.eval_user_chunk
    n = eval_train.shape[0]
    item_chunk = config.eval_item_chunk
    if item_chunk is None and chunk is not None:
        item_chunk = fit_block_m(min(chunk, n), q.shape[1], top_n=10)
    if chunk is None or chunk >= n:
        return evaluate_users(q, eval_train, eval_test,
                              l2=config.l2, alpha=config.alpha,
                              item_chunk=item_chunk)
    sums = np.zeros(4)
    weight = 0.0
    for s in range(0, n, chunk):
        tr, te = eval_train[s:s + chunk], eval_test[s:s + chunk]
        m = evaluate_users(q, tr, te, l2=config.l2, alpha=config.alpha,
                           item_chunk=item_chunk)
        valid = float((np.asarray(te).sum(axis=-1) > 0).sum())
        sums += valid * np.array([float(m.precision), float(m.recall),
                                  float(m.f1), float(m.map)])
        weight += valid
    vals = sums / max(weight, 1.0)
    return RecMetrics(*vals)


def _finalize(setup: _SimSetup, config: FLSimConfig, state: ServerState,
              history: MetricLogger, aux_chunks: List,
              csv_path: Optional[str], hook_failures: int = 0) -> SimResult:
    final = {
        k: history.rolling_mean(k, 10)
        for k in ("precision", "recall", "f1", "map")
    }
    if csv_path:
        history.to_csv()
    rounds = int(state.t)
    # exact byte accounting: the per-round payload is shape-constant, so the
    # totals are rounds x constants. (The traced float32 counters in the
    # state are approximate once totals pass the float32 exact-integer range
    # ~2^24; in-graph consumers needing exact totals at that scale should
    # derive them from state.t x the per-round constants instead.) The
    # per-round constants come from compress.wire_bytes — the same function
    # the traced in-state counters use — so the two can never disagree.
    down_cfg, up_cfg = direction_configs(setup.codec_cfg)
    per_round_down = wire_bytes(
        down_cfg, setup.sel_cfg.num_select, setup.cf_cfg.num_factors)
    per_round_up = wire_bytes(
        up_cfg, setup.sel_cfg.num_select, setup.cf_cfg.num_factors) \
        * setup.cohorts.shape[1]
    selections = rewards = None
    if aux_chunks:
        selections = np.concatenate(
            [np.asarray(a.indices) for a in aux_chunks])
        rewards = np.concatenate([np.asarray(a.rewards) for a in aux_chunks])
    bytes_down = rounds * per_round_down
    bytes_up = rounds * per_round_up
    if config.faults is not None and config.faults.enabled:
        # under faults the uplink is no longer shape-constant per round
        # (survivor renormalization + checksum words), so report the traced
        # in-state totals instead of rounds x constants
        bytes_down = int(float(state.bytes_down))
        bytes_up = int(float(state.bytes_up))
    return SimResult(
        final=final, history=history,
        bytes_down=bytes_down,
        bytes_up=bytes_up,
        rounds=rounds,
        selection_counts=np.asarray(
            selector_counts(setup.sel_cfg, state.sel)),
        selections=selections, rewards=rewards, server_state=state,
        hook_failures=hook_failures,
    )


# ===================================================================== #
# single-run engines
# ===================================================================== #
def run_fcf_simulation(
    train_x: np.ndarray,
    test_x: np.ndarray,
    config: FLSimConfig,
    csv_path: Optional[str] = None,
) -> SimResult:
    """Run one FL simulation with the backend named by ``config.backend``.

    With an enabled ``config.obs``, every committed round's telemetry
    (:mod:`repro.obs.telemetry`) streams to the configured sink: the scan
    engines emit one batched ``io_callback`` per compiled chunk, the
    sharded engine returns the replicated rows and emits host-side, the
    python engine emits per round. Host spans (train_chunk / eval /
    publish) go to ``obs.trace_path`` when set, and ``obs.profile_dir``
    wraps the whole training loop in ``jax.profiler.trace``. Disabled or
    absent, none of this exists in the compiled programs; the spans still
    reach any profiler session that is running, and the round's phases
    carry their ``fl_*`` scope names in the programs' debug info.

    ``train_x``/``test_x`` are dense (N, M) matrices, or CSR triples
    ``(indptr, indices, (N, M))`` of host arrays (scan backend only), which
    set-up lays out on the device once under the span ``lists.layout``.
    """
    if _is_lists(train_x):
        train_j, setup = _lay_out_lists(train_x, test_x, config)
    else:
        train_j = jnp.asarray(train_x, jnp.float32)
        test_j = jnp.asarray(test_x, jnp.float32)
        setup = _build(train_j, test_j, config)
    record = config.record_selections
    obs = config.obs if (config.obs is not None
                         and config.obs.enabled) else None
    prev_tracer = None
    if obs is not None and obs.resolve_tracer() is not None:
        prev_tracer = install_tracer(obs.resolve_tracer())
    try:
        return _run_single(train_j, setup, config, record, obs, csv_path)
    finally:
        if obs is not None:
            try:
                jax.effects_barrier()   # drain pending telemetry callbacks
            except Exception:
                pass
            if obs.resolve_tracer() is not None:
                install_tracer(prev_tracer)


def _boundary(t: int, state: ServerState, setup: _SimSetup,
              config: FLSimConfig, history: MetricLogger) -> int:
    """Evaluate, checkpoint and publish at round ``t``, an eval boundary.
    Returns 1 when the snapshot hook raised, else 0: training continues,
    since a serving publish failure must never abort the round loop."""
    with span("eval", round=t):
        m = _evaluate(state.q, setup.eval_train, setup.eval_test, config)
        history.log(t, **m.as_dict())
    if config.checkpoint_dir is not None:
        save_checkpoint(config.checkpoint_dir, t, state)
    if config.snapshot_hook is None:
        return 0
    try:
        with span("publish", round=t):
            config.snapshot_hook(t, state)
    except Exception:
        log.exception(
            "snapshot_hook raised at round %d; training continues (the "
            "previously published model stays live)", t)
        return 1
    return 0


def _run_single(train, setup, config, record, obs, csv_path) -> SimResult:
    fault_cfg = config.faults
    fault_on = fault_cfg is not None and fault_cfg.enabled
    crash_round = fault_cfg.crash_round if fault_on else None
    start_round = 0
    if config.resume_from is not None:
        path = config.resume_from
        if os.path.isdir(path):
            found = latest_verified_checkpoint(path)
            if found is None:
                raise FileNotFoundError(
                    f"no verified checkpoint to resume from in {path!r}")
            path = found
        start_round = checkpoint_step(path)
        setup = setup._replace(
            state0=load_checkpoint(path, like=setup.state0))
        log.info("resuming from %s at round %d", path, start_round)

    history = MetricLogger(csv_path)
    state = setup.state0
    aux_chunks: List = []
    hook_failures = 0
    profiler = None
    if obs is not None and obs.profile_dir is not None:
        profiler = jax.profiler.trace(obs.profile_dir)
        profiler.__enter__()

    try:
        if config.backend != "python":
            run_chunk, state = _make_chunk_runner(train, setup, config,
                                                  record, obs)
            for start, end in _chunk_bounds(config.rounds,
                                            config.eval_every):
                if end <= start_round:
                    continue    # resume: already committed + checkpointed
                lo = max(start, start_round)
                hi = end
                crash = None
                if crash_round is not None and lo < crash_round <= end:
                    # the host "dies" while executing crash_round: rounds
                    # [lo, crash_round-1] run first and are then LOST —
                    # state never escapes this frame, so resume can only
                    # start from the last checkpoint
                    crash, hi = crash_round, crash_round - 1
                aux = None
                if hi > lo:
                    with span("train_chunk", start=lo, end=hi,
                              backend=config.backend):
                        state, aux = run_chunk(state, lo, hi)
                        if recording():
                            # the recorded span times the chunk's work, not
                            # its enqueue; the profiler's device trace
                            # holds that time without a sync
                            jax.block_until_ready(state)
                if crash is not None:
                    raise SimulatedCrash(crash, config.checkpoint_dir)
                if record:
                    aux_chunks.append(aux)
                hook_failures += _boundary(end, state, setup, config,
                                           history)
        else:  # "python": the per-round-dispatch reference loop
            blocks, _ = _cohort_blocks(config)
            pad_total = _padded_size(setup.cohorts.shape[1], blocks)
            step = jax.jit(_make_round_fn(train, setup, blocks=blocks,
                                          is_async=False,
                                          telemetry=obs is not None))
            if obs is not None:
                emitter = make_row_emitter(obs.resolve_sink(),
                                           obs.telemetry_every)
                tel_step = jax.jit(telemetry_round)
                tel = telemetry_state_init(setup.sel_cfg.num_arms)
            for t in range(start_round + 1, config.rounds + 1):
                if crash_round is not None and t == crash_round:
                    raise SimulatedCrash(crash_round, config.checkpoint_dir)
                rf_t = None
                if fault_on:
                    rf_t = jax.tree.map(
                        lambda a: a[0],
                        round_faults_xs(setup.fault_sched, t - 1, t,
                                        pad_to=pad_total))
                state, aux = step(state, jnp.asarray(setup.cohorts[t - 1]),
                                  rf=rf_t)
                if obs is not None:
                    tel, row = tel_step(tel, aux.telemetry, aux.indices,
                                        aux.rewards)
                    emitter(np.asarray(row))
                    aux = aux._replace(telemetry=())
                if record:
                    aux_chunks.append(jax.tree.map(lambda a: a[None], aux))
                if t % config.eval_every == 0 or t == config.rounds:
                    hook_failures += _boundary(t, state, setup, config,
                                               history)
    finally:
        if profiler is not None:
            profiler.__exit__(None, None, None)

    return _finalize(setup, config, state, history, aux_chunks, csv_path,
                     hook_failures=hook_failures)


# ===================================================================== #
# vmapped sweep entry points
# ===================================================================== #
def run_seed_sweep(
    train_x: np.ndarray,
    test_x: np.ndarray,
    config: FLSimConfig,
    seeds: Sequence[int],
) -> List[SimResult]:
    """Run one config across many seeds as a single vmapped scan program.

    ``train_x``/``test_x`` are either a single (N, M) matrix shared by every
    seed, or stacked (S, N, M) per-seed matrices (the experiment grid's
    rebuild seeds regenerate the dataset too). Every seed gets its own model
    init, selection PRNG stream, cohort schedule and eval cohort (identical
    to what ``run_fcf_simulation`` would use for that seed); the round loop
    executes as ``vmap(scan(server_round_step))`` so the whole rebuild axis
    of an experiment cell costs one compile + one device program.
    """
    if not seeds:
        return []
    if _is_lists(train_x):
        raise ValueError(
            "run_seed_sweep takes the dense (users, items) matrix; per-user "
            "item lists (a CSR triple) run through run_fcf_simulation with "
            "backend='scan'")
    if config.obs is not None and config.obs.enabled:
        raise ValueError(
            "config.obs telemetry is single-run only (one stream per "
            "trajectory); run_seed_sweep vmaps the round engine over seeds "
            "— disable obs or use run_fcf_simulation per seed")
    if config.faults is not None and config.faults.enabled:
        raise ValueError(
            "config.faults is single-run only (per-trajectory fault "
            "schedules and crash/resume semantics); run_seed_sweep vmaps "
            "the round engine over seeds — disable faults or use "
            "run_fcf_simulation per seed")
    train_np = np.asarray(train_x)
    test_np = np.asarray(test_x)
    per_seed_data = train_np.ndim == 3
    if per_seed_data and train_np.shape[0] != len(seeds):
        raise ValueError(
            f"stacked data has {train_np.shape[0]} slices for "
            f"{len(seeds)} seeds")

    def data_for(i):
        if per_seed_data:
            return (jnp.asarray(train_np[i], jnp.float32),
                    jnp.asarray(test_np[i], jnp.float32))
        return (jnp.asarray(train_np, jnp.float32),
                jnp.asarray(test_np, jnp.float32))

    trains = []
    setups = []
    for i, s in enumerate(seeds):
        train_j, test_j = data_for(i)
        trains.append(train_j)
        setups.append(_build(train_j, test_j, replace(config, seed=int(s))))
    setup0 = setups[0]
    record = config.record_selections

    state = jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[s.state0 for s in setups])
    cohorts = np.stack([s.cohorts for s in setups])          # (S, R, B)
    eval_train = jnp.stack([s.eval_train for s in setups])   # (S, E, M)
    eval_test = jnp.stack([s.eval_test for s in setups])
    train_batched = jnp.stack(trains) if per_seed_data else trains[0]

    def scan_chunk(st, ch, train_j):
        round_fn = _make_round_fn(train_j, setup0, blocks=1, is_async=False,
                                  telemetry=False)

        def body(s, cohort):
            s, aux = round_fn(s, cohort)
            return s, (aux if record else None)
        return jax.lax.scan(body, st, ch)

    run_chunk = jax.jit(jax.vmap(
        scan_chunk, in_axes=(0, 0, 0 if per_seed_data else None)))
    if config.eval_user_chunk is None:
        eval_vmapped = jax.jit(jax.vmap(
            lambda q, tr, te: evaluate_users(q, tr, te, l2=config.l2,
                                             alpha=config.alpha)))

        def eval_all(q_stack):
            return eval_vmapped(q_stack, eval_train, eval_test)
    else:
        # memory-bounded chunked eval: per-seed python loop (the vmapped
        # one-shot eval would materialize the full (S, E, M) score tensor,
        # defeating the point of eval_user_chunk)
        def eval_all(q_stack):
            per_seed = [
                _evaluate(q_stack[i], eval_train[i], eval_test[i], config)
                for i in range(len(seeds))
            ]
            return RecMetrics(*[
                jnp.stack([jnp.asarray(float(getattr(m, k)))
                           for m in per_seed])
                for k in ("precision", "recall", "f1", "map")
            ])

    histories = [MetricLogger() for _ in seeds]
    aux_chunks: List = []
    for start, end in _chunk_bounds(config.rounds, config.eval_every):
        state, aux = run_chunk(state, jnp.asarray(cohorts[:, start:end]),
                               train_batched)
        if record:
            aux_chunks.append(aux)
        metrics = eval_all(state.q)
        for i, h in enumerate(histories):
            h.log(end, **{k: float(getattr(metrics, k)[i])
                          for k in ("precision", "recall", "f1", "map")})

    results = []
    for i, s in enumerate(seeds):
        state_i = jax.tree.map(lambda a: a[i], state)
        aux_i = [jax.tree.map(lambda a: a[i], a) for a in aux_chunks]
        results.append(_finalize(setups[i], config, state_i, histories[i],
                                 aux_i, csv_path=None))
    return results


def run_strategy_sweep(
    train_x: np.ndarray,
    test_x: np.ndarray,
    config: FLSimConfig,
    strategies: Sequence[str] = STRATEGIES,
    seeds: Sequence[int] = (0,),
    codecs: Optional[Sequence[str]] = None,
) -> Dict:
    """Sweep strategies (x codecs) x seeds: one vmapped program per cell.

    Strategies carry differently-shaped selector states (and ``full`` a
    different payload width), so the strategy axis is a Python loop over
    compiled seed sweeps rather than a vmap axis; likewise codecs carry
    differently-shaped wire/residual state.

    With ``codecs=None`` (default) every strategy runs ``config.codec`` and
    the result is ``{strategy: [SimResult per seed]}`` — the historical
    shape. With an explicit codec list the result gains the codec axis:
    ``{strategy: {codec: [SimResult per seed]}}``.
    """
    if codecs is None:
        return {
            s: run_seed_sweep(train_x, test_x, replace(config, strategy=s),
                              seeds)
            for s in strategies
        }
    return {
        s: {
            c: run_seed_sweep(
                train_x, test_x, replace(config, strategy=s, codec=c), seeds)
            for c in codecs
        }
        for s in strategies
    }
