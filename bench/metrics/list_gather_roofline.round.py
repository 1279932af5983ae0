"""Share of its roofline that the round's cohort gather reaches when the
interactions are per-user item lists: the least time to read the cohort's
ids once (Theta x d ids, d the mean train degree, plus each user's two
offsets) and write the (Theta, M_s) float32 payload block once, at HBM
bandwidth, over the device time per round of every op under the
``fl_gather`` scope (what ``gather_ms.round`` reads).

    bytes = 4 * Theta * d + 8 * Theta + 4 * Theta * M_s,
    d = num_interactions * train_frac / num_users   (the data block's)

The dense matrix's bound is ``gather_roofline.round``'s. None where no op
carries the scope."""
from bench.harness import scopes
from bench.harness.peaks import roofline_seconds

UNIT = "%"
MOVES = "rounds_per_s"
F32 = 4
I32 = 4


def list_gather_bytes(cfg: dict, num_select: int) -> float:
    """HBM bytes the list gather must move per round, at least."""
    ds, theta = cfg["data"], cfg["theta"]
    d = ds["num_interactions"] * ds["train_frac"] / ds["num_users"]
    return I32 * theta * d + 2 * I32 * theta + F32 * theta * num_select


def read(ctx):
    if not hasattr(ctx, "cell") or not hasattr(ctx, "num_select"):
        return None
    ms = scopes.per_round_ms(ctx, "fl_gather")
    if not ms:
        return None
    nbytes = list_gather_bytes(ctx.cell.config, ctx.num_select)
    bound, _ = roofline_seconds(0.0, nbytes, ctx.device_kind)
    return 100.0 * bound / (ms / 1e3)
