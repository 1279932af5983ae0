"""Synthetic implicit-feedback data at the sizes of the paper's Table 2.

A copy of the program's Table-2 generator (``repro.data.synthetic``), kept
here so the yardstick's data cannot move with the program. Per user i with
log-normal degree n_i (at least ``min_degree``), the n_i items of largest

    score_ij = signal * <u_i, v_j> / sqrt(latent_dim) + pop_j + Gumbel noise

are the interactions (Gumbel-top-k: Plackett-Luce sampling without
replacement, with a Zipf popularity ``pop``); a per-user random 80/20 split
gives train and test.

Every split is made as per-user item lists, CSR: ``indptr`` int64 (n + 1,)
and ``indices`` int32, sorted within each user. The configuration's data
block states the layout the program is handed: ``"dense"`` (the default)
writes the lists into a (users, items) uint8 matrix; ``"lists"`` keeps them,
and then no (users, items) array exists at any point, only the block of
scores being ranked. The lists are cached under ``bench/.cache/data`` so
only the first run in a checkout pays for generating them.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import NamedTuple, Optional, Tuple

import numpy as np

LAYOUTS = ("dense", "lists")
# scores ranked at a time, as (rows x items); the rows of a block are ranked
# on worker threads in tasks of TASK_ROWS
BLOCK_VALUES = int(2e8)
TASK_ROWS = 64


class CSR(NamedTuple):
    """Each user's item ids: user i holds ``indices[indptr[i]:indptr[i+1]]``,
    sorted. A triple ``(indptr, indices, (num_users, num_items))``."""

    indptr: np.ndarray
    indices: np.ndarray
    shape: Tuple[int, int]


def layout(ds: dict) -> str:
    """The layout the data block states: ``"dense"`` unless it says
    ``"lists"``."""
    kind = ds.get("layout", "dense")
    if kind not in LAYOUTS:
        raise ValueError(f"data layout must be one of {LAYOUTS}, got {kind!r}")
    return kind


def user_degrees(num_users: int, num_items: int, num_interactions: int,
                 min_degree: int, rng: np.random.Generator) -> np.ndarray:
    """Log-normal degrees scaled to hit the target interaction count."""
    raw = rng.lognormal(mean=0.0, sigma=1.0, size=num_users)
    deg = np.maximum(min_degree, np.round(raw * num_interactions / raw.sum()))
    deg = np.minimum(deg.astype(np.int64), num_items // 2)
    diff = num_interactions - int(deg.sum())
    if diff > 0:
        bump = rng.integers(0, num_users, size=diff)
        np.add.at(deg, bump, 1)
        deg = np.minimum(deg, num_items // 2)
    return deg


def top_set(neg: np.ndarray, d: int) -> np.ndarray:
    """Sorted ids of the ``d`` smallest values of ``neg`` (negated scores).

    Where the d-th and (d+1)-th smallest tie, which of the tied ids make the
    set depends on order, and the set is the one ``argsort`` picks, as the
    generator always took it."""
    if d <= 0:
        return np.empty(0, np.int64)
    if d >= neg.shape[0]:
        return np.arange(neg.shape[0])
    part = np.argpartition(neg, (d - 1, d))
    if neg[part[d - 1]] == neg[part[d]]:
        part = np.argsort(neg)
    return np.sort(part[:d])


def interactions(ds: dict, seed: int, block_values: Optional[int] = None,
                 workers: Optional[int] = None) -> CSR:
    """Each user's interacted item ids.

    The Gumbel draws follow the degrees in the generator's stream, row-major
    over (users, items), one 64-bit output each; a task draws its rows from
    a copy of the stream advanced to them, so the data does not depend on
    the block size or the number of worker threads."""
    n, m, k0 = ds["num_users"], ds["num_items"], ds["latent_dim"]
    rng = np.random.default_rng(seed)
    u = rng.standard_normal((n, k0)).astype(np.float32)
    v = rng.standard_normal((m, k0)).astype(np.float32)
    ranks = rng.permutation(m) + 1
    pop = (-ds["zipf_exponent"] * np.log(ranks)).astype(np.float32)
    deg = user_degrees(n, m, ds["num_interactions"], ds["min_degree"], rng)
    gumbel_at = rng.bit_generator.state

    def gumbel(lo: int, hi: int) -> np.ndarray:
        bits = type(rng.bit_generator)()
        bits.state = gumbel_at
        bits.advance(lo * m)
        return np.random.Generator(bits).gumbel(size=(hi - lo, m))

    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = np.empty(int(indptr[-1]), np.int32)
    block_rows = max(1, (block_values or BLOCK_VALUES) // m)
    workers = workers or min(8, os.cpu_count() or 1)
    scale = ds["signal"] / np.sqrt(k0)

    def rank(dots: np.ndarray, start: int, lo: int, hi: int) -> None:
        scores = scale * dots[lo - start:hi - start] + pop[None, :]
        neg = -(scores + gumbel(lo, hi).astype(np.float32))
        for i in range(lo, hi):
            indices[indptr[i]:indptr[i + 1]] = top_set(neg[i - lo], deg[i])

    with ThreadPoolExecutor(workers) as pool:
        for start in range(0, n, block_rows):
            stop = min(start + block_rows, n)
            dots = u[start:stop] @ v.T
            tasks = [pool.submit(rank, dots, start, lo,
                                 min(lo + TASK_ROWS, stop))
                     for lo in range(start, stop, TASK_ROWS)]
            for t in tasks:
                t.result()
    return CSR(indptr, indices, (n, m))


def _cut(count: int, train_frac: float) -> int:
    """How many of a user's ``count`` items go to train: at least one, and
    at least one left for test where there are two or more."""
    if count <= 1:
        return count
    return min(max(1, int(round(train_frac * count))), count - 1)


def split(x: CSR, train_frac: float, seed: int) -> Tuple[CSR, CSR]:
    """Per-user random split of each user's interacted items."""
    rng = np.random.default_rng(seed)
    n = x.shape[0]
    counts = np.diff(x.indptr)
    cuts = np.array([_cut(int(c), train_frac) for c in counts], np.int64)
    tr_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(cuts, out=tr_ptr[1:])
    te_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts - cuts, out=te_ptr[1:])
    tr_idx = np.empty(int(tr_ptr[-1]), np.int32)
    te_idx = np.empty(int(te_ptr[-1]), np.int32)
    for i in range(n):
        items = x.indices[x.indptr[i]:x.indptr[i + 1]].astype(np.int64)
        rng.shuffle(items)
        tr_idx[tr_ptr[i]:tr_ptr[i + 1]] = np.sort(items[:cuts[i]])
        te_idx[te_ptr[i]:te_ptr[i + 1]] = np.sort(items[cuts[i]:])
    return CSR(tr_ptr, tr_idx, x.shape), CSR(te_ptr, te_idx, x.shape)


def densify(x: CSR) -> np.ndarray:
    """The lists written into a (users, items) uint8 matrix."""
    out = np.zeros(x.shape, np.uint8)
    out[np.repeat(np.arange(x.shape[0]), np.diff(x.indptr)), x.indices] = 1
    return out


def rows(x: CSR, ids: np.ndarray) -> np.ndarray:
    """The users ``ids``' rows of the (users, items) matrix, float32."""
    out = np.zeros((len(ids), x.shape[1]), np.float32)
    for r, i in enumerate(ids):
        out[r, x.indices[x.indptr[i]:x.indptr[i + 1]]] = 1.0
    return out


def _load(path: Path, shape: Tuple[int, int]):
    with np.load(path) as z:
        if tuple(z["shape"]) != shape:
            return None
        return tuple(CSR(z[f"{s}_indptr"], z[f"{s}_indices"], shape)
                     for s in ("train", "test"))


def dataset(ds: dict, cache_dir: Path):
    """``(train, test)`` of the configuration's data block in its layout:
    uint8 (users, items) matrices, or a :class:`CSR` each; generated once
    per checkout and then read back from the cache."""
    kind = layout(ds)
    shape = (ds["num_users"], ds["num_items"])
    path = cache_dir / f"{ds['name']}-{ds['seed']}.csr.npz"
    got = _load(path, shape) if path.is_file() else None
    if got is None:
        x = interactions(ds, ds["seed"])
        got = split(x, ds["train_frac"], ds["seed"] + 1)
        cache_dir.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp.npz")
        np.savez(tmp, shape=np.asarray(shape),
                 **{f"{s}_{f}": getattr(c, f)
                    for s, c in zip(("train", "test"), got)
                    for f in ("indptr", "indices")})
        tmp.replace(path)
    if kind == "lists":
        return got
    return densify(got[0]), densify(got[1])
