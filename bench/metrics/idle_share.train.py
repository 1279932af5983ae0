"""Share of the traced training window in which no op ran on the chip:
1 - busy / window from the profiler trace; the longest gaps go to the
result's breakdown, named by what the host was doing."""
UNIT = "%"
MOVES = "rounds_per_s"


def read(ctx):
    summary = getattr(ctx, "summary", None)
    if summary is None or summary.window_s <= 0:
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)
