"""Share of its roofline that the round's cohort gather reaches: the least
time to read the cohort's Theta user rows of the dense float32 interaction
matrix whole (Theta x M values) and write the (Theta, M_s) payload block
once, at HBM bandwidth, over the device time per round of every op under
the ``fl_gather`` scope (what ``gather_ms.round`` reads).

Whole rows, because on the dense matrix the M_s payload columns, a tenth
of M picked across the whole catalog, fall in nearly every (8, 128) tile
of a user's row. A layout that holds fewer bytes per user (per-user item
lists) has to restate the bound. None where no op carries the scope."""
from bench.harness import counts, scopes
from bench.harness.peaks import roofline_seconds

UNIT = "%"
MOVES = "rounds_per_s"


def read(ctx):
    if not hasattr(ctx, "cell") or not hasattr(ctx, "num_select"):
        return None
    ms = scopes.per_round_ms(ctx, "fl_gather")
    if not ms:
        return None
    cfg = ctx.cell.config
    theta, m = cfg["theta"], cfg["data"]["num_items"]
    nbytes = counts.F32 * theta * (m + ctx.num_select)
    bound, _ = roofline_seconds(0.0, nbytes, ctx.device_kind)
    return 100.0 * bound / (ms / 1e3)
