"""FL server for FCF — Algorithm 1, functional core + legacy shim.

Primary API (jit/scan/vmap-safe):

  * :class:`ServerState` — the entire server as a pure pytree: global model
    Q, per-row Adam state, selector state, PRNG key, round counter, and
    byte counters carried as traced scalars.
  * :func:`server_init` — build a fresh state.
  * :func:`server_round_step` — ONE fused FL round (Alg. 1 lines 8-19):
    select -> gather Q* (Pallas payload gather) -> cohort local solve ->
    fused item gradients -> scatter-based sparse Adam commit -> reward /
    BTS posterior update. Pure ``(state, cohort_x) -> (state, aux)``, so the
    simulation can drive thousands of rounds through ``jax.lax.scan`` and
    vectorize whole sweeps with ``jax.vmap``.
  * :func:`server_round_step_async` — the staleness-bounded async round:
    every round PUBLISHES a fresh encoded snapshot Q* into a bounded ring
    buffer (``ServerState.snapshots``, wire images so depth-S bounding costs
    S payload-sized buffers, not S full tables) and COMMITS a cohort that
    solved against the snapshot of ``staleness`` rounds ago — via a
    staleness-discounted Adam step and a delay-corrected bandit reward
    attributed to the stale pull (the paper's deployment model, where users
    report back asynchronously). ``staleness=0`` reduces bit-for-bit to the
    synchronous step.

:class:`FCFServer` is the original mutable, Python-driven server kept as a
backwards-compatible shim (incremental ``begin_round``/``receive`` protocol
with Theta-threshold accumulation across multiple cohort receipts); it now
also routes its payload download through the kernel gather.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.lax import optimization_barrier

from repro.cf.local import solve_user_factors
from repro.cf.model import CFConfig
from repro.compress import (
    CHECKSUM_BYTES_PER_ROW, CodecConfig, QuantWire, codec_state_init, decode,
    direction_configs, encode, encode_with_residual, is_stateful,
    row_checksums, verify_rows, wire_bytes,
)
from repro.faults import fault_state_update, flip_row_bits
from repro.core.payload import PayloadSelector
from repro.core.selector import (
    AsyncSelectorState, SelectorConfig, SelectorState, async_selector_init,
    pending_lookup, pending_record, pull_stats, selector_init,
    selector_observe, selector_select,
)
from repro.kernels import ops
from repro.obs.telemetry import RoundTelemetry
from repro.optim.adam import (
    AdamConfig, AdamState, adam_init, adam_update_rows,
    adam_update_rows_scattered,
)
from repro.optim.state_compress import MomentCodecConfig, needs_sr_key

# fold_in salt deriving the per-commit stochastic-rounding key from the
# round's selection key (only when the moment config statically needs one,
# so fp32 programs never see the extra fold)
_MOMENT_KEY_SALT = 0x6d71    # "mq"


class FCFServerConfig(NamedTuple):
    theta: int = 100              # federated updates needed per global update
    adam: AdamConfig = AdamConfig(
        lr=0.01, beta1=0.1, beta2=0.99, eps=1e-8)   # paper Table 3
    # Bandit feedback (beyond-paper fix, ablatable): each user's Eq. 6
    # gradient carries a +2λq_j term; aggregated over Θ users the feedback
    # becomes  data_term + 2λΘ·q_j.  The λ part is popularity-INDEPENDENT
    # noise ∝ |q_j| that swamps the informative data term at early rounds —
    # measured corr(reward, popularity) = -0.35 at t=1, locking the bandit
    # onto uninformative items (worse than FCF-Random on MIND-scale data).
    # The server knows λ, Θ and Q*, so it subtracts 2λΘ·q_j from the
    # FEEDBACK ONLY (the model update keeps the paper's exact Eq. 4);
    # no extra client information is used.  "raw" reproduces the paper.
    reward_feedback: str = "data_term"          # "data_term" | "raw"
    l2: float = 1.0
    # async engine: a commit against a snapshot s rounds stale scales its
    # Adam step by discount**s (FedAsync-style exponential damping; 1.0
    # disables damping, 0.0 makes stale commits step-free). s=0 commits are
    # always undamped (discount**0 == 1.0 exactly). 0.8 measured best on the
    # movielens-mini staleness curves (benchmarks/async_cohorts.py): heavy
    # damping (0.5) costs more P@10 than the staleness it guards against on
    # a smooth simulated cohort stream.
    staleness_discount: float = 0.8
    # optimizer-state storage (repro.optim.state_compress): how Adam's
    # per-row moments live in memory. None (and the all-fp32 config) is the
    # frozen fp32 path — bit-identical programs to every historical run.
    # Compressed options (bf16 / int8-with-per-row-scales / SM3-factored v)
    # shrink the resident optimizer state below the model itself at
    # 10M-item scale; static config, never part of the scan carry.
    moment: Optional[MomentCodecConfig] = None


class ServerState(NamedTuple):
    """The whole FL server as a pure pytree (scan carry / vmap axis)."""

    q: jax.Array            # (M, K) global model Q^T
    opt: AdamState          # per-row Adam moments + timesteps
    sel: SelectorState      # strategy-specific selector state
    key: jax.Array          # PRNG key driving the selection stream
    t: jax.Array            # () int32 — committed global rounds
    # cumulative payload bytes as traced float32 scalars. NOTE: float32 is
    # exact only up to 2^24; past that the running totals round to the local
    # ulp. The payload is shape-constant per round, so exact totals are
    # always recoverable as t x per-round bytes (what SimResult reports).
    bytes_down: jax.Array   # () float32 — cumulative payload downlink bytes
    bytes_up: jax.Array     # () float32 — cumulative payload uplink bytes
    # payload codec state: the (M, K) error-feedback residual for stateful
    # codecs (topk uplink sparsification), the empty pytree () otherwise —
    # either way a fixed-shape scan carry / vmap axis
    codec: Any = ()
    # async engine only: bounded ring of the last max_staleness+1 ENCODED
    # downlink snapshots (wire pytree leaves with a leading (slots,) axis —
    # S int8 snapshots cost S payload-sized wire images, not S full (M, K)
    # tables). The empty pytree () for the synchronous backends.
    snapshots: Any = ()
    # fault layer only (repro.faults): a FaultState of cumulative degradation
    # counters — dropped clients, stragglers, checksum-rejected rows,
    # retransmit bytes — carried as traced scalars exactly like the byte
    # counters. The empty pytree () whenever fault injection is off, which
    # keeps the carry structure (and every compiled program) identical to a
    # faultless build.
    faults: Any = ()


class RoundAux(NamedTuple):
    """Per-round outputs surfaced by the fused step (scan ``ys``)."""

    indices: jax.Array      # (M_s,) selected arms
    rewards: jax.Array      # (M_s,) bandit rewards (zeros for non-learners)
    # RoundTelemetry when the step is built with telemetry=True, else the
    # empty pytree — the default keeps the pytree structure (and therefore
    # every compiled program and shard out_spec) identical to a build
    # without the obs layer
    telemetry: Any = ()


class ShardContext(NamedTuple):
    """Static description of one FL round's data-parallel execution.

    Inside ``shard_map`` over a 1-D ``(axis,)`` device mesh, every (M, K)
    table (global model Q, Adam moments, BTS reward buffers, codec residual)
    is row-sharded into ``rows_per_shard = M // num_shards`` blocks, the
    cohort is split into ``num_shards`` user blocks (one per device), and all
    small control state (selector posteriors, PRNG key, byte counters) is
    replicated. See :func:`server_round_step` for the collective schedule.
    """

    axis: str               # mesh axis name the tables/cohort shard over
    num_shards: int         # D — devices on the axis
    rows_per_shard: int     # M // D rows of each (M, K) table per device


def shard_row_ops(shard: ShardContext) -> ops.RowOps:
    """Collective-aware row ops over row-sharded (M, K) tables.

    gather: each shard block-gathers a full (M_s, K) candidate (clamped
    local indices, one kernel pass over its own rows), the candidates are
    all-gathered, and the owner-select keeps each row from the one shard
    that holds it — pure data movement, so the assembled rows are bit-equal
    to a single-device gather. scatter_set: shard-local drop-scatter of the
    rows this shard owns (no collective; every shard already holds the full
    (M_s, K) update replicated).
    """
    def gather(table: jax.Array, idx: jax.Array) -> jax.Array:
        cand = ops.gather_rows_block(table, _local_idx(shard, idx))
        # barrier per the RowOps contract: consumers must see the same
        # materialized producer graph as the single-device gather
        return optimization_barrier(assemble_rows(shard, idx, cand))

    def scatter_set(table: jax.Array, idx: jax.Array,
                    rows: jax.Array) -> jax.Array:
        return ops.scatter_set_rows_block(table, _local_idx(shard, idx), rows)

    return ops.RowOps(gather=gather, scatter_set=scatter_set)


def _local_idx(shard: ShardContext, idx: jax.Array) -> jax.Array:
    """Global payload indices -> this shard's local row coordinates."""
    d = jax.lax.axis_index(shard.axis)
    return idx.astype(jnp.int32) - d * shard.rows_per_shard


def assemble_rows(shard: ShardContext, idx: jax.Array,
                  candidate: jax.Array) -> jax.Array:
    """All-gather per-shard candidate blocks and keep each row's owner copy.

    ``candidate`` is this shard's (M_s, ...) block-gather result (rows it
    does not own are clamp artifacts). The all-gather moves the candidate in
    whatever format it is in — for the int8 downlink that is the quantized
    wire image, 4x fewer bytes on the interconnect than fp32 rows — and the
    owner-select is exact (selection, not summation), so the assembled block
    is bit-identical to the single-device gather.
    """
    gathered = jax.lax.all_gather(candidate, shard.axis, axis=0)  # (D, M_s, .)
    owner = (idx.astype(jnp.int32) // shard.rows_per_shard)[None, :, None]
    return jnp.take_along_axis(gathered, owner, axis=0)[0]


def snapshot_ring_init(
    codec_cfg: CodecConfig, slots: int, num_rows: int, dim: int
) -> Any:
    """All-zero ring of ``slots`` encoded downlink snapshots.

    Leaves mirror the downlink wire format with a leading (slots,) axis, so
    the ring is a fixed-shape scan carry whose size is ``slots`` payload
    wire images (codes + scales for int8, halves for fp16, ...). Zero slots
    are never decoded: the async staleness schedule clamps s <= t-1, so
    every slot is published before it is first committed against.
    """
    down_cfg, _ = direction_configs(codec_cfg)
    proto = encode(down_cfg, jnp.zeros((num_rows, dim), jnp.float32))
    return jax.tree.map(
        lambda leaf: jnp.zeros((slots,) + leaf.shape, leaf.dtype), proto)


def _ring_put(ring: Any, slot: jax.Array, wire: Any) -> Any:
    """Overwrite ring ``slot`` (traced index) with a fresh wire image."""
    return jax.tree.map(
        lambda r, w: jax.lax.dynamic_update_index_in_dim(r, w, slot, 0),
        ring, wire)


def _ring_get(ring: Any, slot: jax.Array) -> Any:
    """The wire image stored in ring ``slot`` (traced index)."""
    return jax.tree.map(
        lambda r: jax.lax.dynamic_index_in_dim(r, slot, 0, keepdims=False),
        ring)


class EncodedSnapshot(NamedTuple):
    """One published downlink snapshot, still in its wire format.

    The serving publish artifact: ``wire`` holds the encoded payload rows
    exactly as the async engine pushed them into the ring (int8 codes +
    per-row scales, fp16 halves, ...), ``indices`` names the global item
    rows they cover, ``t`` is the publish round. Consumers that keep their
    model in wire format (:class:`repro.serve.ServingModel`) install these
    rows without ever decoding to fp32 — per-row encoding makes the row
    patch bit-identical to re-encoding the patched dense table.
    """

    t: jax.Array            # () int32 — publish round
    indices: jax.Array      # (M_s,) int32 — global rows the wire covers
    wire: Any               # downlink wire pytree for those rows


def latest_snapshot(state: ServerState) -> EncodedSnapshot:
    """The freshest ring entry of an async-engine state (no decode).

    After round ``t`` commits, the newest published snapshot lives in ring
    slot ``rem(t-1, slots)`` and its pull is recorded in the selector's
    pending-attribution buffer — both are popped here as-is. Requires a
    state built with ``server_init(async_slots=...)`` that has run at least
    one round (slot 0 is all-zero before the first publish).
    """
    sel_async = state.sel
    assert isinstance(sel_async, AsyncSelectorState), (
        "latest_snapshot needs a state built with "
        "server_init(async_slots=...)")
    slots = sel_async.pending.t.shape[0]
    slot = jax.lax.rem(state.t - 1, slots)
    idx, t_pub = pending_lookup(sel_async.pending, slot)
    return EncodedSnapshot(
        t=t_pub, indices=idx, wire=_ring_get(state.snapshots, slot))


def server_init(
    item_factors: jax.Array,
    sel_cfg: SelectorConfig,
    key: jax.Array,
    config: FCFServerConfig = FCFServerConfig(),
    codec_cfg: CodecConfig = CodecConfig(),
    async_slots: Optional[int] = None,
    force_residual: bool = False,
) -> ServerState:
    """Fresh server state around an initialized global model.

    ``async_slots`` (= ``max_staleness + 1``) equips the state for the
    async engine: the selector is wrapped with a pending-attribution buffer
    and the encoded-snapshot ring is allocated. ``None`` (synchronous)
    leaves both as empty pytrees.

    ``force_residual`` allocates the (M, K) error-feedback residual even for
    stateless codecs — required by the fault layer's corruption path, where
    checksum-rejected rows are retained in the residual for retransmit no
    matter which codec runs the uplink.
    """
    # config is static hyper-parameters — only the moment-storage choice
    # shapes the state pytree (compressed AdamState leaves)
    sel: Any = selector_init(sel_cfg)
    snapshots: Any = ()
    if async_slots is not None:
        sel = async_selector_init(sel_cfg, async_slots)
        snapshots = snapshot_ring_init(
            codec_cfg, async_slots, sel_cfg.num_select,
            item_factors.shape[1])
    return ServerState(
        q=item_factors,
        opt=adam_init(item_factors, per_row=True, moment=config.moment),
        sel=sel,
        key=key,
        t=jnp.zeros((), jnp.int32),
        bytes_down=jnp.zeros((), jnp.float32),
        bytes_up=jnp.zeros((), jnp.float32),
        codec=codec_state_init(
            codec_cfg, item_factors.shape[0], item_factors.shape[1],
            force_residual=force_residual),
        snapshots=snapshots,
    )


def _downlink_wire(state_q: jax.Array, idx: jax.Array, down_cfg: CodecConfig,
                   shard: Optional[ShardContext]):
    """Gather + encode the payload rows Q* into their wire image.

    Single device: one kernel pass over the resident table (fused
    gather+quantize for int8). Sharded: each device encodes the candidate
    rows of its own block *first* and only then all-gathers, so the
    collective moves the wire image (int8 codes + per-row scales for int8,
    fp16 halves for fp16) instead of fp32 rows — the "all-gather the
    selected-and-compressed rows, not the table" schedule. Encoding is
    per-row, so owner-selected rows are bit-identical to a single-device
    encode.
    """
    if shard is None:
        if down_cfg.name == "int8":
            # hot path: fused gather+quantize kernel (one HBM trip per row)
            return QuantWire(*ops.gather_quantize_rows(state_q, idx))
        return encode(down_cfg, ops.gather_rows(state_q, idx))
    local = _local_idx(shard, idx)
    if down_cfg.name == "int8":
        wire_local = QuantWire(*ops.gather_quantize_rows_block(state_q, local))
    else:
        wire_local = encode(down_cfg, ops.gather_rows_block(state_q, local))
    return jax.tree.map(lambda leaf: assemble_rows(shard, idx, leaf),
                        wire_local)


def server_round_step(
    state: ServerState,
    cohort_x,                      # (B, M) cohort rows, or idx -> cohort blocks
    *,
    sel_cfg: SelectorConfig,
    config: FCFServerConfig,
    cf_cfg: CFConfig,
    codec_cfg: CodecConfig = CodecConfig(),
    num_users: Optional[int] = None,
    shard: Optional[ShardContext] = None,
    telemetry: bool = False,
    faults: Any = None,
) -> Tuple[ServerState, RoundAux]:
    """One fused FL round (Alg. 1 lines 8-19) as a pure function.

    ``faults`` (a :class:`repro.faults.RoundFaults`, default ``None``)
    activates this round's slice of the pre-sampled fault schedule: the
    driver has already zeroed dropped/straggling users out of ``cohort_x``
    and passes the traced survivor count as ``num_users`` (gradient
    renormalization over survivors); here the wire-corruption schedule
    drives the checksum reject path in the commit core, the per-user uplink
    cost grows by the checksum word, and the cumulative degradation
    counters on ``state.faults`` advance. ``None`` compiles the historical
    program byte-for-byte.

    ``telemetry`` (static) additionally surfaces a :class:`RoundTelemetry`
    of traced in-step scalars on ``RoundAux.telemetry`` — wire bytes,
    gradient/update norms, arm-pull coverage, and (under ``shard_map``) the
    psum-reduced per-round collective bytes. The default ``False`` adds no
    ops at all: the obs layer's disabled-path bit-parity contract.

    The cohort of B users stands in for the asynchronous arrival of exactly
    Theta federated updates that triggers a global commit; the server only
    ever sees the aggregated gradient (the paper's privacy model).

    ``cohort_x`` is either the dense (B, M) cohort slice of the interaction
    matrix, or a callable mapping the selected indices (M_s,) to the cohort's
    column subset directly — the lazy form lets the driver fuse the
    user-row/item-column gather into one indexed read instead of
    materializing (B, M) per round (a real cost at web-scale M). The callable
    may return either a flat (B, M_s) block or pre-blocked (C, b, M_s) user
    blocks; padded user rows (all-zero x) contribute exactly zero to every
    aggregate, so drivers pad the cohort to equal blocks and pass the true
    cohort size as ``num_users``.

    CLIENT PHASE BLOCKING. The cohort solve + item gradients are computed
    per user block, and the per-block partial gradients are reduced in fixed
    block order behind a ``lax.optimization_barrier`` (the barrier pins the
    reduction boundary so XLA cannot refuse the blocks' materialization and
    re-fuse the sum into a differently-ordered accumulation). This makes the
    round's float semantics a function of the *block structure only*: a
    single device scanning C blocks and a ``shard_map`` mesh solving one
    block per device over C devices produce bit-identical trajectories —
    the all-gather of partials followed by the same ordered sum is exactly
    an order-fixed psum.

    Bit-parity caveat: the contract is enforced (by tier-1 test) for the
    fp32/fp16/int8 codecs across every strategy. The int4/topk *programs*
    fuse their unpack/sparsify chains into the moment-update loops, and
    XLA:CPU's FMA-contraction choice inside those fusions can differ
    between the sharded and single-device programs — trajectories then
    agree to float32 contraction ulps (~1e-7 relative) rather than
    bit-for-bit. Selections and wire bytes remain identical.

    SHARDED EXECUTION (``shard`` set, inside ``shard_map``): the (M, K)
    tables in ``state`` (Q, Adam moments, BTS reward buffers, codec
    residual) are row-sharded over ``shard.axis``; selection and all small
    state are replicated. Per round only payload-sized tensors cross the
    interconnect: the encoded Q* candidates (all-gather), the (M_s, K)
    partial gradients (all-gather == ordered psum), and the row gathers of
    the Adam/reward/residual tables; every scatter commit is shard-local.

    ``codec_cfg`` names the wire format for the item-dependent payload
    (:mod:`repro.compress`). Every transmitted tensor physically goes
    through encode->decode, so clients solve against the *decoded* Q* and
    the server commits the *decoded* gradients — quality degradation from
    lossy codecs is real, not just accounted. The int8 downlink routes
    through the fused gather+quantize Pallas kernel; stateful codecs carry
    their error-feedback residual in ``state.codec`` (residual rows are
    gathered/scattered with the payload kernels alongside Q). In the
    simulation the cohort-aggregated uplink gradient is encoded once — the
    wire image of the aggregate each of the ``B`` users' updates passes
    through — and the per-user byte accounting multiplies that row cost
    by ``B``, exactly like the dense accounting did.
    """
    down_cfg, up_cfg = direction_configs(codec_cfg)
    m_s = sel_cfg.num_select
    kdim = state.q.shape[1]

    # lines 8-10: select the payload subset, gather + encode + "transmit" Q*;
    # clients decode the wire image, so q_star below is what they compute on
    with jax.named_scope("fl_select"):
        key, k_sel = jax.random.split(state.key)
        idx, sel = selector_select(sel_cfg, state.sel, k_sel)
    with jax.named_scope("fl_downlink"):
        q_star = decode(down_cfg,
                        _downlink_wire(state.q, idx, down_cfg, shard),
                        kdim)                                # (M_s, K)
        q_star = optimization_barrier(q_star)
        bytes_down = state.bytes_down + wire_bytes(down_cfg, m_s, kdim)

    # lines 11-18: cohort solve, uplink, Adam commit, reward feedback.
    # The stochastic-rounding dither key only exists when the moment config
    # statically requires one — fp32 programs trace no extra PRNG ops.
    moment_key = (jax.random.fold_in(k_sel, _MOMENT_KEY_SALT)
                  if needs_sr_key(config.moment) else None)
    has_corrupt = faults is not None and not isinstance(faults.corrupt, tuple)
    q_new, opt, sel, codec_state, rewards, num_users, stats, intact = \
        _commit_against(
            state, sel, idx, q_star, cohort_x, sel_cfg=sel_cfg, config=config,
            cf_cfg=cf_cfg, up_cfg=up_cfg, num_users=num_users, shard=shard,
            want_stats=telemetry,
            corrupt=faults.corrupt if has_corrupt else None,
            moment_key=moment_key)
    with jax.named_scope("fl_commit"):
        per_user_bytes = wire_bytes(up_cfg, m_s, kdim)
        if has_corrupt:
            per_user_bytes += m_s * CHECKSUM_BYTES_PER_ROW
        bytes_up = state.bytes_up + per_user_bytes * num_users

        fault_state = state.faults
        if faults is not None:
            rejected = (jnp.zeros((), jnp.float32) if intact is None
                        else jnp.sum(~intact).astype(jnp.float32))
            fault_state = fault_state_update(
                state.faults, faults.dropped, faults.stragglers, rejected,
                rejected * float(wire_bytes(up_cfg, 1, kdim)
                                 + CHECKSUM_BYTES_PER_ROW))

    new_state = ServerState(
        q=q_new, opt=opt, sel=sel, key=key, t=state.t + 1,
        bytes_down=bytes_down, bytes_up=bytes_up, codec=codec_state,
        snapshots=state.snapshots, faults=fault_state,
    )
    aux_tel: Any = ()
    if telemetry:
        aux_tel = _round_telemetry(
            new_state, sel_cfg, down_cfg, up_cfg, m_s, kdim, num_users,
            shard, stats,
            staleness=jnp.zeros((), jnp.float32),
            step_weight=jnp.ones((), jnp.float32))
    return new_state, RoundAux(indices=idx, rewards=rewards,
                               telemetry=aux_tel)


def _round_telemetry(
    new_state: ServerState,
    sel_cfg: SelectorConfig,
    down_cfg: CodecConfig,
    up_cfg: CodecConfig,
    m_s: int,
    kdim: int,
    num_users,
    shard: Optional[ShardContext],
    stats,
    *,
    staleness: jax.Array,
    step_weight: jax.Array,
) -> RoundTelemetry:
    """Assemble one round's :class:`RoundTelemetry` (telemetry=True only).

    ``collective_bytes`` prices what each shard puts on the interconnect
    per round — its encoded Q* candidate block plus its fp32 partial
    gradient block, both (M_s,)-sized — psum-reduced over the mesh axis so
    every shard reports the same mesh-total. 0 off-mesh.
    """
    if shard is None:
        collective = jnp.zeros((), jnp.float32)
    else:
        per_shard = jnp.float32(
            wire_bytes(down_cfg, m_s, kdim) + m_s * kdim * 4)
        collective = jax.lax.psum(per_shard, shard.axis)
    arms_explored, pull_max = pull_stats(sel_cfg, new_state.sel)
    grad_norm, update_norm = stats
    return RoundTelemetry(
        t=new_state.t,
        staleness=jnp.asarray(staleness, jnp.float32),
        step_weight=jnp.asarray(step_weight, jnp.float32),
        bytes_down=jnp.float32(wire_bytes(down_cfg, m_s, kdim)),
        bytes_up=jnp.float32(wire_bytes(up_cfg, m_s, kdim))
        * jnp.asarray(num_users, jnp.float32),
        collective_bytes=collective,
        grad_norm=grad_norm,
        update_norm=update_norm,
        arms_explored=arms_explored,
        pull_max=pull_max,
    )


def _commit_against(
    state: ServerState,
    sel: SelectorState,
    idx: jax.Array,                # (M_s,) payload rows the cohort solved on
    q_star: jax.Array,             # (M_s, K) decoded snapshot they solved with
    cohort_x,                      # (B, M) rows, or idx -> cohort blocks
    *,
    sel_cfg: SelectorConfig,
    config: FCFServerConfig,
    cf_cfg: CFConfig,
    up_cfg: CodecConfig,
    num_users: Optional[int],
    shard: Optional[ShardContext],
    t_obs: Optional[jax.Array] = None,
    step_weight: Optional[jax.Array] = None,
    want_stats: bool = False,
    corrupt: Optional[jax.Array] = None,
    moment_key: Optional[jax.Array] = None,
):
    """Alg. 1 lines 11-18 against a given (idx, Q*) pair — the commit core.

    Shared verbatim by the synchronous and async round steps: the sync step
    passes the snapshot it just published (``t_obs=None``, no step weight);
    the async step passes a *stale* snapshot popped from the ring plus its
    pull round (delay-corrected reward) and the staleness discount for the
    Adam step. Returns ``(q, opt, sel, codec_state, rewards, num_users,
    stats, intact)`` with ``stats`` a traced ``(grad_norm, update_norm)``
    pair when ``want_stats`` (telemetry) is on and ``None`` otherwise — the
    extra row gathers behind the norms are only ever traced when requested,
    so the default program is unchanged.

    ``corrupt`` ((M_s,) bool, the fault layer's pre-sampled wire-corruption
    schedule) activates payload integrity verification: the encoded uplink
    wire gets a per-row checksum, the scheduled rows have one bit flipped in
    transit, and rows whose received checksum mismatches are REJECTED — the
    model/moment/reward commit treats them as never received (exact no-op
    rows via ``row_mask``) while the error-feedback residual retains their
    full effective gradient for retransmit next round. Requires a state
    built with ``server_init(force_residual=True)`` so the residual exists
    for stateless codecs too. ``intact`` is the (M_s,) bool accept mask
    (``None`` when ``corrupt`` is ``None``, which compiles the historical
    program byte-for-byte).
    """
    row_ops = ops.default_row_ops() if shard is None else shard_row_ops(shard)
    kdim = state.q.shape[1]

    # line 11: every cohort user solves p_i on-device and uplinks gradients;
    # the server receives the cohort aggregate, assembled block-by-block
    with jax.named_scope("fl_gather"):
        if callable(cohort_x):
            x_blocks = cohort_x(idx)             # (C, b, M_s) or (B, M_s)
        else:
            x_blocks = jnp.take(cohort_x, idx, axis=1)       # (B, M_s)
        if x_blocks.ndim == 2:
            x_blocks = x_blocks[None]                        # one block
    if num_users is None:
        num_users = x_blocks.shape[0] * x_blocks.shape[1]
    parts = []
    for i in range(x_blocks.shape[0]):
        with jax.named_scope("fl_solve"):
            p_i = solve_user_factors(q_star, x_blocks[i],
                                     l2=cf_cfg.l2, alpha=cf_cfg.alpha)
        # data term only (l2=0): the ridge term is applied once, below, with
        # the true cohort size — padded all-zero user rows solve to p=0 and
        # contribute exactly zero here
        with jax.named_scope("fl_grad"):
            parts.append(ops.fcf_item_gradients(
                q_star, p_i, x_blocks[i], alpha=cf_cfg.alpha, l2=0.0))
    with jax.named_scope("fl_grad"):
        parts = jnp.stack(parts)                             # (C, M_s, K)
        if shard is not None:
            # ordered psum: all-gather the per-device partials and reduce
            # in fixed block order — bit-stable against the single-device
            # scan over the same blocks (a raw lax.psum orders by topology)
            parts = jax.lax.all_gather(parts, shard.axis, axis=0,
                                       tiled=True)
        parts = optimization_barrier(parts)
        grads = (jnp.sum(parts, axis=0)
                 + 2.0 * cf_cfg.l2 * num_users * q_star)     # (M_s, K)
    with jax.named_scope("fl_commit"):
        # uplink encode (+ error feedback for stateful codecs): the server
        # only ever sees the decoded wire image of the aggregated gradient
        codec_state = state.codec
        intact = None
        if corrupt is not None:
            # payload integrity path: checksum the encoded wire, flip the
            # scheduled rows' bits in transit, reject rows whose received
            # image no longer matches. Rejected rows keep their full
            # effective gradient in the residual so the next round's encode
            # retransmits them; accepted rows behave exactly like the
            # faultless codec path.
            res_rows = row_ops.gather(codec_state, idx)      # (M_s, K)
            eff = grads + res_rows
            wire = encode(up_cfg, eff)
            decoded = decode(up_cfg, wire, kdim)
            sums = row_checksums(wire)
            received = flip_row_bits(wire, corrupt)
            intact = verify_rows(received, sums)             # (M_s,) bool
            keep = intact[:, None]
            grads_hat = jnp.where(keep, decoded, 0.0)
            if is_stateful(up_cfg):
                new_res = jnp.where(keep, eff - decoded, eff)
            else:
                new_res = jnp.where(keep, jnp.zeros_like(eff), eff)
            codec_state = row_ops.scatter_set(codec_state, idx, new_res)
        elif is_stateful(up_cfg):
            res_rows = row_ops.gather(codec_state, idx)      # (M_s, K)
            _, grads_hat, new_res = encode_with_residual(up_cfg, grads,
                                                         res_rows)
            codec_state = row_ops.scatter_set(codec_state, idx, new_res)
        else:
            grads_hat = decode(up_cfg, encode(up_cfg, grads), kdim)
        grads_hat = optimization_barrier(grads_hat)

        # line 13: sparse Adam commit on the selected rows (scatter
        # kernels; shard-local scatters against the row-sharded tables when
        # sharded), step-discounted by staleness under the async engine
        q_new, opt = adam_update_rows_scattered(
            grads_hat, idx, state.opt, state.q, config.adam, row_ops=row_ops,
            row_weights=step_weight, row_mask=intact,
            moment=config.moment, moment_key=moment_key)

        # lines 14-18: reward feedback + posterior update — on the decoded
        # gradients (the only thing a codec-running server would have),
        # delay-corrected to the pull round when the feedback arrived stale
        feedback = grads_hat
        if config.reward_feedback == "data_term":
            feedback = optimization_barrier(
                grads_hat - 2.0 * config.l2 * num_users * q_star)
        sel, rewards = selector_observe(sel_cfg, sel, idx, feedback,
                                        row_ops=row_ops, t_obs=t_obs,
                                        row_mask=intact)
        stats = None
        if want_stats:
            delta = row_ops.gather(q_new, idx) - row_ops.gather(state.q, idx)
            stats = (jnp.linalg.norm(grads_hat), jnp.linalg.norm(delta))
    return q_new, opt, sel, codec_state, rewards, num_users, stats, intact


def server_round_step_async(
    state: ServerState,
    cohort_x,                      # (B, M) cohort rows, or idx -> cohort blocks
    staleness: jax.Array,          # () int32 — this commit's snapshot age
    *,
    sel_cfg: SelectorConfig,
    config: FCFServerConfig,
    cf_cfg: CFConfig,
    codec_cfg: CodecConfig = CodecConfig(),
    num_users: Optional[int] = None,
    shard: Optional[ShardContext] = None,
    telemetry: bool = False,
    faults: Any = None,
) -> Tuple[ServerState, RoundAux]:
    """One staleness-bounded ASYNC round: publish fresh, commit stale.

    ``faults`` mirrors :func:`server_round_step`'s fault hook: the
    corruption schedule gates the commit core's checksum reject path (the
    stale commit's wire rows are the ones corrupted — faults hit arriving
    traffic, whatever round it was pulled in), survivors/``num_users`` were
    applied by the driver, and the degradation counters advance on
    ``state.faults``. ``None`` compiles the historical program
    byte-for-byte.

    ``telemetry`` (static) mirrors :func:`server_round_step`'s flag; the
    async telemetry additionally reports this commit's snapshot age and
    the ``staleness_discount ** s`` step weight it applied.

    The paper's deployment model has users reporting back asynchronously;
    this step simulates it with the cohort block as the async unit. Each
    round the server

      1. PUBLISHES: pulls a fresh payload subset, encodes Q* into its wire
         image and pushes it into the bounded snapshot ring
         (``state.snapshots``, ``slots = max_staleness + 1``), recording the
         pull in the selector's pending-attribution buffer;
      2. COMMITS: pops the snapshot published ``staleness`` rounds ago —
         the cohort that reports back this round solved against THAT
         (possibly stale) Q* — and runs the exact synchronous commit core
         against it, with two async corrections: the Adam step is scaled by
         ``staleness_discount ** s`` (:func:`adam_update_rows_scattered`'s
         per-row weights) and the bandit reward is attributed to the arm
         pulls of the snapshot round (``selector_observe(t_obs=...)``).

    ``staleness`` must satisfy ``0 <= s <= min(max_staleness, t-1)`` — the
    driver's schedule guarantees it, so every popped slot was pushed first.
    Clients decode the ring's wire image, so a stale int8 snapshot is the
    same lossy tensor a real stale client would hold.

    With ``staleness == 0`` every round, the popped snapshot is the one
    just pushed, the discount is exactly 1.0 and ``t_obs`` equals the
    current round: the trajectory is bit-identical to
    :func:`server_round_step` at equal cohort blocking (tier-1 contract,
    ``tests/test_async_cohorts.py``). Under ``shard_map`` the ring and
    pending buffer are replicated (payload-sized) while the tables stay
    row-sharded — a stale block is just a block solved against an older Q*,
    so the sharded collective schedule is unchanged.

    Sharded-async parity caveat (same class as the sync engine's int4/topk
    note in :func:`server_round_step`): at ``staleness=0`` the sharded async
    program is bit-identical to the single-device async scan for every
    strategy and codec, and stays bit-identical at s > 0 for int8. For the
    raw-fp32 downlink at s > 0, XLA:CPU's contraction choices around the
    ring slice differ between the two programs and trajectories agree to
    float32 ulps (~1e-9 absolute on Q) rather than bit-for-bit; selections
    and wire bytes remain identical. Enforced by
    ``tests/test_async_cohorts.py``'s fake-device subprocess matrix.
    """
    down_cfg, up_cfg = direction_configs(codec_cfg)
    m_s = sel_cfg.num_select
    kdim = state.q.shape[1]
    sel_async = state.sel
    assert isinstance(sel_async, AsyncSelectorState), (
        "server_round_step_async needs a state built with "
        "server_init(async_slots=...)")
    slots = sel_async.pending.t.shape[0]

    # publish: fresh pull, encode, push wire + pending attribution. The
    # barrier pins the wire image's producer graph at the push — the popped
    # snapshot must decode from the same materialized bits no matter which
    # round (or which shard program) consumes it.
    t_now = state.t + 1
    slot_now = jax.lax.rem(t_now - 1, slots)
    with jax.named_scope("fl_select"):
        key, k_sel = jax.random.split(state.key)
        idx, inner = selector_select(sel_cfg, sel_async.inner, k_sel)
        pending = pending_record(sel_async.pending, slot_now, idx, t_now)
    with jax.named_scope("fl_downlink"):
        wire_now = optimization_barrier(
            _downlink_wire(state.q, idx, down_cfg, shard))
        ring = _ring_put(state.snapshots, slot_now, wire_now)
        bytes_down = state.bytes_down + wire_bytes(down_cfg, m_s, kdim)

        # commit: pop the snapshot `staleness` rounds back and solve
        # against it
        s = jnp.asarray(staleness, jnp.int32)
        slot_old = jax.lax.rem(t_now - 1 - s, slots)
        idx_s, t_s = pending_lookup(pending, slot_old)
        q_star = decode(down_cfg, _ring_get(ring, slot_old), kdim)
        q_star = optimization_barrier(q_star)
    with jax.named_scope("fl_commit"):
        step_weight = jnp.full(
            (m_s,),
            jnp.power(jnp.float32(config.staleness_discount),
                      s.astype(jnp.float32)))
    moment_key = (jax.random.fold_in(k_sel, _MOMENT_KEY_SALT)
                  if needs_sr_key(config.moment) else None)
    has_corrupt = faults is not None and not isinstance(faults.corrupt, tuple)
    q_new, opt, inner, codec_state, rewards, num_users, stats, intact = \
        _commit_against(
            state, inner, idx_s, q_star, cohort_x, sel_cfg=sel_cfg,
            config=config, cf_cfg=cf_cfg, up_cfg=up_cfg, num_users=num_users,
            shard=shard, t_obs=t_s, step_weight=step_weight,
            want_stats=telemetry,
            corrupt=faults.corrupt if has_corrupt else None,
            moment_key=moment_key)
    with jax.named_scope("fl_commit"):
        per_user_bytes = wire_bytes(up_cfg, m_s, kdim)
        if has_corrupt:
            per_user_bytes += m_s * CHECKSUM_BYTES_PER_ROW
        bytes_up = state.bytes_up + per_user_bytes * num_users

        fault_state = state.faults
        if faults is not None:
            rejected = (jnp.zeros((), jnp.float32) if intact is None
                        else jnp.sum(~intact).astype(jnp.float32))
            fault_state = fault_state_update(
                state.faults, faults.dropped, faults.stragglers, rejected,
                rejected * float(wire_bytes(up_cfg, 1, kdim)
                                 + CHECKSUM_BYTES_PER_ROW))

    new_state = state._replace(
        q=q_new, opt=opt,
        sel=AsyncSelectorState(inner=inner, pending=pending),
        key=key, t=t_now, bytes_down=bytes_down, bytes_up=bytes_up,
        codec=codec_state, snapshots=ring, faults=fault_state,
    )
    aux_tel: Any = ()
    if telemetry:
        aux_tel = _round_telemetry(
            new_state, sel_cfg, down_cfg, up_cfg, m_s, kdim, num_users,
            shard, stats,
            staleness=s.astype(jnp.float32), step_weight=step_weight[0])
    return new_state, RoundAux(indices=idx_s, rewards=rewards,
                               telemetry=aux_tel)


# ===================================================================== #
# Legacy mutable shim (incremental receive protocol)
# ===================================================================== #
@dataclass
class FCFServer:
    """Mutable Python-driven server (legacy shim over the pure pieces).

    Unlike :func:`server_round_step` (one fused call per round), this keeps
    the incremental protocol: ``begin_round()`` exposes Q*, any number of
    ``receive`` calls accumulate cohort gradients, and the Theta-threshold
    triggers the commit — matching a real deployment's asynchronous arrivals.
    """

    item_factors: jax.Array            # (M, K) global model Q^T
    selector: PayloadSelector
    config: FCFServerConfig = field(default_factory=FCFServerConfig)

    opt_state: Optional[AdamState] = None
    _selected: Optional[jax.Array] = None          # current round's item ids
    _grad_accum: Optional[jax.Array] = None        # (M_s, K) accumulated grads
    _updates_accum: int = 0                        # NumberGradientUpdates
    rounds_committed: int = 0
    bytes_down: int = 0                            # payload accounting
    bytes_up: int = 0

    def __post_init__(self):
        if self.opt_state is None:
            from repro.optim.state_compress import is_compressed
            if is_compressed(self.config.moment):
                raise ValueError(
                    "the legacy FCFServer shim only supports fp32 optimizer "
                    "state; compressed moment configs need the fused round "
                    "engine (server_init / server_round_step)")
            self.opt_state = adam_init(self.item_factors, per_row=True)

    # ---------------------------------------------------------------- #
    def begin_round(self) -> jax.Array:
        """Select the payload subset and return Q* rows (Alg. 1 lines 8-10)."""
        self._selected = self.selector.select()
        q_star = ops.gather_rows(self.item_factors, self._selected)
        self.bytes_down += q_star.size * q_star.dtype.itemsize
        return q_star

    @property
    def selected(self) -> jax.Array:
        assert self._selected is not None, "call begin_round() first"
        return self._selected

    def receive(self, grad_rows: jax.Array, num_users: int) -> bool:
        """Accumulate a cohort's aggregated gradient (Alg. 1 line 11).

        Returns True if this receipt triggered a global-model commit.
        """
        assert self._selected is not None, "call begin_round() first"
        # each participating user uplinks its own (M_s, K) gradient
        self.bytes_up += grad_rows.size * grad_rows.dtype.itemsize * num_users
        if self._grad_accum is None:
            self._grad_accum = grad_rows
        else:
            self._grad_accum = self._grad_accum + grad_rows
        self._updates_accum += num_users
        if self._updates_accum >= self.config.theta:
            self._commit()
            return True
        return False

    # ---------------------------------------------------------------- #
    def _commit(self) -> None:
        """Global update + bandit feedback (Alg. 1 lines 13-19)."""
        idx, grads = self._selected, self._grad_accum
        q_star = ops.gather_rows(self.item_factors, idx)
        # line 13: Q <- Q - eta * sum_i grad_i (Adam-adapted, Eq. 4)
        self.item_factors, self.opt_state = adam_update_rows(
            grads, idx, self.opt_state, self.item_factors, self.config.adam
        )
        # lines 14-18: v update, rewards, BTS posterior, prev-grad buffer
        feedback = grads
        if self.config.reward_feedback == "data_term":
            feedback = grads - 2.0 * self.config.l2 * self._updates_accum \
                * q_star
        self.selector.observe(idx, feedback)
        self.rounds_committed += 1
        self._grad_accum = None
        self._updates_accum = 0

    # ---------------------------------------------------------------- #
    @property
    def num_items(self) -> int:
        return self.item_factors.shape[0]

    @property
    def num_factors(self) -> int:
        return self.item_factors.shape[1]
