"""Share of its roofline that the ``fcf_grad`` kernel reaches in the trace:
the least time the chip needs for the cohort gradient's operations and
bytes (``bench.harness.counts``), over the kernel's device time. At the
cells' shapes the bytes term bounds it."""
from bench.harness import counts, trace
from bench.harness.peaks import roofline_seconds

UNIT = "%"
MOVES = "rounds_per_s"


def read(ctx):
    summary = getattr(ctx, "summary", None)
    if summary is None or not hasattr(ctx, "num_select"):
        return None
    calls, secs = trace.kernel_seconds(summary, ["fcf_grad"])
    if not calls or secs <= 0:
        return None
    cfg = ctx.cell.config
    theta, k, m_s = cfg["theta"], cfg["num_factors"], ctx.num_select
    bound, _ = roofline_seconds(counts.fcf_grad_ops(theta, m_s, k),
                                counts.fcf_grad_bytes(theta, m_s, k),
                                ctx.device_kind)
    return 100.0 * calls * bound / secs
