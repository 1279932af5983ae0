"""The numbers that decide ``correct``, each held to a limit of its own.

Training: the program's state after its first chunk against the plain
reference after as many rounds from the same seed.

  change_gap     the gap between the program's norm of the model's change
                 (Q - Q0, the one parameter leaf) and the reference's,
                 against the reference's
  state_gap      (full) the worst state leaf's gap between the program's
                 norm and the reference's (Adam's m and v), against the
                 reference's norm of that leaf or of the median state leaf,
                 whichever is larger
  pull_mismatch  (BTS) the share of arm pulls in which the two selection
                 streams differ: sum|n_program - n_reference| / (2 sum n)

Under BTS the state leaves are not compared: each holds, per row, what the
last round that pulled the row left there, so one arm pulled in another
round replaces whole rows, and the gap of norms swings from seed to seed by
which row that was. The pulls themselves are compared instead.

Serving: every sampled answer against the reference's top-N of its user.

  rank_gap       the widest gap, at any rank, by which the served item's
                 reference score lies below the reference's item of that
                 rank, against the user's best reference score; a seen or
                 unknown item scores -inf and reads inf
  score_err      the widest difference between a served score and the
                 reference's score of the served item, on the same scale
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict

import numpy as np

TRAIN_LEAVES = ("m", "v")
BTS_LEAVES = ("reward_sum", "reward_v", "prev_grad")


def _norm(a) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64).ravel()))


def leaf_gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
              strategy: str) -> Dict[str, float]:
    """Each leaf's gap between the program's norm and the reference's:
    ``q_change`` against the reference's norm of it, each state leaf against
    the reference's norm of that leaf or of the median state leaf,
    whichever is larger."""
    q0 = ref["q0"]
    moved = _norm(ref["q"] - q0)
    out = {"q_change": abs(_norm(prog["q"] - q0) - moved) / moved
           if moved else math.inf}
    names = TRAIN_LEAVES + (BTS_LEAVES if strategy == "bts" else ())
    ref_norms = {k: _norm(ref[k]) for k in names}
    median = float(np.median(list(ref_norms.values())))
    out.update({k: abs(_norm(prog[k]) - ref_norms[k]) / max(ref_norms[k],
                                                             median)
                for k in names})
    return out


def training_numbers(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
                     strategy: str) -> Dict[str, float]:
    gaps = leaf_gaps(prog, ref, strategy)
    out = {"change_gap": gaps.pop("q_change")}
    if strategy != "bts":
        out["state_gap"] = max(gaps.values())
    else:
        n_ref = np.asarray(ref["counts"], np.float64)
        diff = np.abs(np.asarray(prog["counts"], np.float64) - n_ref).sum()
        out["pull_mismatch"] = float(diff / (2.0 * n_ref.sum()))
    return out


def serving_numbers(served_scores: np.ndarray, served_ids: np.ndarray,
                    ref_scores: np.ndarray, ref_ids: np.ndarray
                    ) -> Dict[str, float]:
    """All arrays are (B, ...) for one request: served (B, N) scores and ids,
    the reference's (B, M) scores (-inf on seen items) and (B, N) top ids."""
    b, n = served_ids.shape
    m = ref_scores.shape[1]
    rows = np.arange(b)[:, None]
    best = np.take_along_axis(ref_scores, ref_ids, axis=1)        # (B, N)
    scale = np.maximum(np.abs(best[:, :1]), 1e-30)
    valid = (served_ids >= 0) & (served_ids < m)
    got = np.where(valid, ref_scores[rows, np.clip(served_ids, 0, m - 1)],
                   -np.inf)
    gap = np.where(np.isfinite(got), (best - got) / scale, np.inf)
    err = np.where(np.isfinite(got), np.abs(served_scores - got) / scale,
                   np.inf)
    return {"rank_gap": float(np.max(gap)), "score_err": float(np.max(err))}


def load_limits(root: Path, cell: str) -> Dict[str, float]:
    """The cell's limits; a cell without a limits file has none, so every
    number it compares fails."""
    path = root / "bench" / "limits" / f"{cell}.json"
    if not path.is_file():
        return {}
    with open(path) as f:
        return {k: float(v["limit"]) for k, v in json.load(f).items()}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """``{name: {"value", "limit"}}`` for every number, and whether all are
    within their limits (a number without a limit is a failure)."""
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in numbers.items()}
    ok = all(k in limits and v <= limits[k] for k, v in numbers.items())
    return {"checks": checks, "ok": bool(ok) and bool(numbers)}
