"""95th percentile over the window's requests of the wait from the due time
until a worker called ``recommend`` (harness stamps)."""
from bench.harness.stats import percentile

UNIT = "ms"
MOVES = "serve_p95_ms"


def read(ctx):
    lat = getattr(ctx, "latencies", None)
    if not lat or not lat["queue_ms"]:
        return None
    return percentile(lat["queue_ms"], 95)
