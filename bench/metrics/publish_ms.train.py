"""Median time of the publisher call at each chunk boundary of the window,
from the harness's stamps around ``ServingEngine.publisher()`` until the
installed model is ready."""
import statistics

UNIT = "ms"
MOVES = "rounds_per_s"


def read(ctx):
    pubs = getattr(ctx, "publish_s", None)
    if not pubs:
        return None
    return 1e3 * statistics.median(pubs)
