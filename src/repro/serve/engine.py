"""Request batching + snapshot publish/swap around a :class:`ServingModel`.

Two serving-infrastructure concerns live here, deliberately outside the
pure model:

  * REQUEST BATCHING — recommendation requests arrive at arbitrary batch
    sizes, but every distinct shape costs one XLA compile. The engine pads
    each request up to a fixed bucket ladder (``buckets``), so steady-state
    traffic hits a handful of compiled programs no matter the request mix;
    oversized requests chunk over the largest bucket. Padded user rows are
    all-zero factor vectors whose results are sliced off before returning.
  * SNAPSHOT PUBLISH/SWAP — training publishes encoded payload rows
    (the async ring's :class:`repro.cf.server.EncodedSnapshot` entries);
    ``publisher()`` patches them into the wire-resident model and
    atomically swaps the result in. The swap is a single reference
    assignment under a lock with a monotonically bumped version;
    in-flight requests keep the model value they grabbed at entry (JAX
    arrays are immutable), so readers see either the old or the new model
    in full — never a mix (tested in tests/test_serving.py).

The model/version pair only changes together under ``_lock``; the jit
cache is keyed on (bucket, M, codec) shapes, so a swap to a same-shape
model never recompiles.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.obs.config import ObsConfig
from repro.obs.hist import LatencyHistogram
from repro.obs.prom import Metric, render
from repro.obs.trace import span
from repro.serve.model import ServingModel
from repro.utils.logging import get_logger

log = get_logger("repro.serve")

DEFAULT_BUCKETS = (8, 64, 256)


class LoadShedError(RuntimeError):
    """A request was refused admission (queue full or deadline exceeded).

    ``reason`` is ``"queue"`` or ``"deadline"`` — the same label the
    ``frs_serve_shed_total`` Prometheus counter is partitioned by."""

    def __init__(self, message: str, reason: str):
        self.reason = reason
        super().__init__(message)


class ServeStats(NamedTuple):
    """Engine counters (monotonic since construction)."""

    requests: int           # recommend() calls
    users: int              # real (unpadded) user rows served
    installs: int           # snapshot/model swaps
    version: int            # current model version
    # trailing defaults keep historical positional constructions valid
    shed: int = 0           # requests refused admission (queue + deadline)
    publish_failures: int = 0   # failed snapshot-install attempts


class ServingEngine:
    """Batched, hot-swappable serving front-end over a wire-format model."""

    def __init__(
        self,
        model: ServingModel,
        *,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        top_n: int = 10,
        block_m: int = 1024,
        obs: Optional[ObsConfig] = None,
        max_inflight: Optional[int] = None,
        admission_deadline_s: Any = None,
        publish_max_retries: int = 2,
        publish_backoff_s: float = 0.05,
    ):
        if not buckets or any(b <= 0 for b in buckets):
            raise ValueError(f"buckets must be positive, got {buckets!r}")
        if max_inflight is not None and max_inflight <= 0:
            raise ValueError(
                f"max_inflight must be positive, got {max_inflight!r}")
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.top_n = int(top_n)
        self.block_m = int(block_m)
        # load-shedding knobs: a bounded admission queue (max_inflight
        # concurrent recommend() calls; None = unbounded) and per-request
        # admission deadlines (seconds a request may have waited before
        # entry; a float applies to every bucket, a {bucket: seconds} dict
        # sets per-bucket budgets — larger buckets usually afford less
        # queueing since they cost more to score)
        self.max_inflight = None if max_inflight is None else int(max_inflight)
        self.admission_deadline_s = admission_deadline_s
        self.publish_max_retries = int(publish_max_retries)
        self.publish_backoff_s = float(publish_backoff_s)
        self._lock = threading.Lock()
        self._model = model
        self._requests = 0
        self._users = 0
        self._installs = 0
        self._shed_queue = 0
        self._shed_deadline = 0
        self._publish_failures = 0
        self._publish_retries = 0
        # observability: metrics() renders regardless, but per-request
        # latency timing (a device sync per bucket chunk) only runs with an
        # enabled obs config — the read path is untouched otherwise
        self._obs_on = obs is not None and obs.enabled
        self._lat: Dict[int, LatencyHistogram] = {
            b: LatencyHistogram() for b in self.buckets}
        self._inflight = 0
        self._snapshot_age = -1     # rounds; -1 = never published

    # ------------------------------------------------------------- #
    # model access + publish/swap
    # ------------------------------------------------------------- #
    @property
    def model(self) -> ServingModel:
        with self._lock:
            return self._model

    def swap(self, model: ServingModel) -> ServingModel:
        """Atomically install ``model`` as the live serving model."""
        with self._lock:
            if model.version <= self._model.version:
                model = model._replace(version=self._model.version + 1)
            self._model = model
            self._installs += 1
            return model

    def publish_rows(self, indices: jax.Array, rows_wire: Any) -> ServingModel:
        """Patch encoded payload rows into the live model and swap."""
        return self.swap(self.model.install_rows(indices, rows_wire))

    def publisher(self):
        """A ``(round, ServerState) -> None`` hook for ``FLSimConfig
        .snapshot_hook``: publishes each eval-boundary state into this
        engine. Async-engine states publish their freshest encoded ring
        snapshot — the wire rows themselves, never a decoded fp32 Q* —
        while synchronous states (no ring) re-encode the full table.

        Degradation contract: a failed install is retried up to
        ``publish_max_retries`` times with exponential backoff; if every
        attempt fails the hook logs, bumps ``frs_serve_publish_failures_
        total``, and RETURNS — the previously installed model version
        stays live and the exception never propagates into the training
        loop (which has its own containment, but should not need it for
        serving-side faults).
        """
        def hook(round_: int, state) -> None:
            attempts = self.publish_max_retries + 1
            for attempt in range(attempts):
                if attempt:
                    with self._lock:
                        self._publish_retries += 1
                    time.sleep(self.publish_backoff_s * 2 ** (attempt - 1))
                try:
                    with span("publish_snapshot", round=round_,
                              attempt=attempt):
                        cur = self.model
                        with span("publish.encode"):
                            if state.snapshots != ():
                                from repro.cf.server import latest_snapshot
                                snap = latest_snapshot(state)
                                model = cur.install_snapshot(snap)
                                age = (round_ - int(snap.t) if self._obs_on
                                       else 0)
                            else:
                                model = ServingModel.from_dense(
                                    cur.cfg, state.q, version=cur.version + 1)
                                age = 0  # sync states publish their live table
                        with span("publish.install"):
                            self.swap(model)
                    with self._lock:
                        self._snapshot_age = age
                    return
                except Exception:
                    with self._lock:
                        self._publish_failures += 1
                    log.exception(
                        "snapshot install attempt %d/%d failed at round %d",
                        attempt + 1, attempts, round_)
            log.error(
                "giving up on round %d snapshot publish after %d attempts; "
                "previous model version stays live", round_, attempts)

        return hook

    def stats(self) -> ServeStats:
        with self._lock:
            return ServeStats(requests=self._requests, users=self._users,
                              installs=self._installs,
                              version=self._model.version,
                              shed=self._shed_queue + self._shed_deadline,
                              publish_failures=self._publish_failures)

    # ------------------------------------------------------------- #
    # observability
    # ------------------------------------------------------------- #
    def latency_histogram(self) -> LatencyHistogram:
        """All bucket histograms merged (exact) — one engine-wide view.

        Populated only when the engine was built with an enabled obs
        config; empty (``total == 0``) otherwise.
        """
        with self._lock:
            hists = [h.copy() for h in self._lat.values()]
        merged = hists[0]
        for h in hists[1:]:
            merged = merged.merge(h)
        return merged

    def metrics(self) -> str:
        """Prometheus text exposition of the engine's counters, gauges and
        per-bucket latency histograms (format 0.0.4).

        Always renders — latency histograms just stay empty without an
        enabled obs config. Thread-safe against concurrent ``recommend``/
        ``swap`` calls: everything is copied under the lock, so a scrape
        sees one consistent cut (counters monotone across scrapes).
        """
        with self._lock:
            model = self._model
            requests, users = self._requests, self._users
            installs, inflight = self._installs, self._inflight
            age = self._snapshot_age
            shed_q, shed_d = self._shed_queue, self._shed_deadline
            pub_fail, pub_retry = self._publish_failures, self._publish_retries
            hists = [({"bucket": str(b)}, h.copy())
                     for b, h in sorted(self._lat.items())]
        families = [
            Metric("frs_serve_requests_total", "counter",
                   "recommend() calls served", [({}, requests)]),
            Metric("frs_serve_users_total", "counter",
                   "real (unpadded) user rows served", [({}, users)]),
            Metric("frs_serve_installs_total", "counter",
                   "model snapshot installs (swap count)",
                   [({}, installs)]),
            Metric("frs_serve_queue_depth", "gauge",
                   "recommend() calls currently in flight",
                   [({}, inflight)]),
            Metric("frs_serve_model_version", "gauge",
                   "live serving model version", [({}, model.version)]),
            Metric("frs_serve_snapshot_age_rounds", "gauge",
                   "age in rounds of the last published snapshot "
                   "(-1 = never published)", [({}, age)]),
            Metric("frs_serve_resident_bytes", "gauge",
                   "wire-resident serving model bytes",
                   [({}, model.resident_bytes())]),
            Metric("frs_serve_shed_total", "counter",
                   "requests refused admission, by reason",
                   [({"reason": "queue"}, shed_q),
                    ({"reason": "deadline"}, shed_d)]),
            Metric("frs_serve_publish_failures_total", "counter",
                   "failed snapshot-install attempts", [({}, pub_fail)]),
            Metric("frs_serve_publish_retries_total", "counter",
                   "snapshot-install retry attempts", [({}, pub_retry)]),
            Metric("frs_serve_latency_seconds", "histogram",
                   "recommend latency per padded request bucket",
                   hists=hists),
        ]
        return render(families)

    # ------------------------------------------------------------- #
    # batched reads
    # ------------------------------------------------------------- #
    def _bucket_for(self, b: int) -> int:
        for size in self.buckets:
            if b <= size:
                return size
        return self.buckets[-1]

    def _deadline_for(self, bucket: int) -> Optional[float]:
        d = self.admission_deadline_s
        if d is None:
            return None
        if isinstance(d, dict):
            v = d.get(bucket)
            return None if v is None else float(v)
        return float(d)

    def recommend(
        self,
        p: jax.Array,                             # (B, K) user factors
        top_n: Optional[int] = None,
        train_mask: Optional[jax.Array] = None,   # (B, M); 1 = exclude
        admitted_at: Optional[float] = None,      # time.monotonic() at enqueue
    ) -> Tuple[jax.Array, jax.Array]:
        """Top-N items for a batch of users: ``(scores, ids)``, best first.

        The request is padded up to the bucket ladder (or chunked over the
        largest bucket) and scored against ONE model value grabbed at
        entry, so a concurrent publish never splits a request across model
        versions.

        Load shedding: when ``admitted_at`` (a ``time.monotonic()`` stamp
        taken where the request entered the system) is older than the
        bucket's admission deadline, or ``max_inflight`` requests are
        already executing, the request is refused with
        :class:`LoadShedError` before any scoring work — shedding stale or
        excess load costs O(1), keeping admitted-request latency bounded.
        """
        n = self.top_n if top_n is None else int(top_n)
        b = p.shape[0]
        if admitted_at is not None:
            deadline = self._deadline_for(self._bucket_for(b))
            if deadline is not None \
                    and time.monotonic() - admitted_at > deadline:
                with self._lock:
                    self._shed_deadline += 1
                raise LoadShedError(
                    f"request of {b} users exceeded its {deadline}s "
                    f"admission deadline", reason="deadline")
        with self._lock:
            # check-and-increment under one lock acquisition: the bounded
            # queue can never over-admit between a check and a later bump
            if self.max_inflight is not None \
                    and self._inflight >= self.max_inflight:
                self._shed_queue += 1
                raise LoadShedError(
                    f"{self._inflight} requests in flight "
                    f"(max_inflight={self.max_inflight})", reason="queue")
            self._inflight += 1
        model = self.model           # one consistent view for the request
        timed = self._obs_on
        try:
            with span("serve_batch", users=b):
                out_v, out_i = [], []
                step = self.buckets[-1]
                for start in range(0, b, step):
                    pc = p[start:start + step]
                    mc = None if train_mask is None \
                        else train_mask[start:start + step]
                    if timed:
                        t0 = time.perf_counter()
                        v, i = self._run_bucket(model, pc, mc, n)
                        jax.block_until_ready((v, i))
                        dt = time.perf_counter() - t0
                        with self._lock:
                            self._lat[self._bucket_for(pc.shape[0])] \
                                .record(dt)
                    else:
                        v, i = self._run_bucket(model, pc, mc, n)
                    out_v.append(v)
                    out_i.append(i)
        finally:
            with self._lock:
                self._inflight -= 1
        with self._lock:
            self._requests += 1
            self._users += b
        if len(out_v) == 1:
            return out_v[0], out_i[0]
        return jnp.concatenate(out_v), jnp.concatenate(out_i)

    def _run_bucket(self, model: ServingModel, p: jax.Array,
                    mask: Optional[jax.Array], top_n: int):
        b = p.shape[0]
        size = self._bucket_for(b)
        if b < size:
            p = jnp.pad(p, ((0, size - b), (0, 0)))
            if mask is not None:
                mask = jnp.pad(mask, ((0, size - b), (0, 0)))
        vals, idx = model.topn(p, top_n, train_mask=mask,
                               block_m=self.block_m)
        return vals[:b], idx[:b]
