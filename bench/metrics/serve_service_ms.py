"""95th percentile over the window's requests of the time inside
``recommend`` until the result was ready (harness stamps)."""
from bench.harness.stats import percentile

UNIT = "ms"
MOVES = "serve_p95_ms"


def read(ctx):
    lat = getattr(ctx, "latencies", None)
    if not lat or not lat["service_ms"]:
        return None
    return percentile(lat["service_ms"], 95)
