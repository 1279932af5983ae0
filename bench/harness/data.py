"""Synthetic implicit-feedback data at the sizes of the paper's Table 2.

A copy of the program's Table-2 generator (``repro.data.synthetic``), kept
here so the yardstick's data cannot move with the program. Per user i with
log-normal degree n_i (at least ``min_degree``), the n_i items of largest

    score_ij = signal * <u_i, v_j> / sqrt(latent_dim) + pop_j + Gumbel noise

are the interactions (Gumbel-top-k: Plackett-Luce sampling without
replacement, with a Zipf popularity ``pop``); a per-user random 80/20 split
gives train and test. The configuration file states every size and the data
seed; the arrays are cached bit-packed under ``bench/.cache/data`` so only
the first run in a checkout pays for generating them.
"""
from __future__ import annotations

from pathlib import Path
from typing import Tuple

import numpy as np


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


def interactions(ds: dict, seed: int) -> np.ndarray:
    """Dense binary interaction matrix (users, items) as uint8."""
    n, m, k0 = ds["num_users"], ds["num_items"], ds["latent_dim"]
    rng = np.random.default_rng(seed)
    u = rng.standard_normal((n, k0)).astype(np.float32)
    v = rng.standard_normal((m, k0)).astype(np.float32)
    ranks = rng.permutation(m) + 1
    pop = (-ds["zipf_exponent"] * np.log(ranks)).astype(np.float32)
    deg = user_degrees(n, m, ds["num_interactions"], ds["min_degree"], rng)
    x = np.zeros((n, m), dtype=np.uint8)
    chunk = max(1, int(2e8) // m)
    for start in range(0, n, chunk):
        stop = min(start + chunk, n)
        scores = (ds["signal"] / np.sqrt(k0)) * (u[start:stop] @ v.T) \
            + pop[None, :]
        noisy = scores + rng.gumbel(size=scores.shape).astype(np.float32)
        order = np.argsort(-noisy, axis=1)
        for r, i in enumerate(range(start, stop)):
            x[i, order[r, :deg[i]]] = 1
    return x


def split(x: np.ndarray, train_frac: float, seed: int
          ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-user random split of each user's interacted items."""
    rng = np.random.default_rng(seed)
    train = np.zeros_like(x)
    test = np.zeros_like(x)
    for i in range(x.shape[0]):
        items = np.flatnonzero(x[i])
        rng.shuffle(items)
        cut = max(1, int(round(train_frac * len(items))))
        cut = min(cut, len(items) - 1) if len(items) > 1 else cut
        train[i, items[:cut]] = 1
        test[i, items[cut:]] = 1
    return train, test


def dataset(ds: dict, cache_dir: Path) -> Tuple[np.ndarray, np.ndarray]:
    """``(train, test)`` uint8 matrices of the configuration's data block,
    generated once per checkout and then read back from the cache."""
    n, m = ds["num_users"], ds["num_items"]
    path = cache_dir / f"{ds['name']}-{ds['seed']}.npz"
    if path.is_file():
        with np.load(path) as z:
            train = np.unpackbits(z["train"], axis=1, count=m)
            test = np.unpackbits(z["test"], axis=1, count=m)
        if train.shape == (n, m):
            return train, test
    x = interactions(ds, ds["seed"])
    train, test = split(x, ds["train_frac"], ds["seed"] + 1)
    cache_dir.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp.npz")
    np.savez(tmp, train=np.packbits(train, axis=1),
             test=np.packbits(test, axis=1))
    tmp.replace(path)
    return train, test
