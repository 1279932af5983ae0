"""Share of their roofline that the payload row kernels reach together
(``gather_rows``, ``gather_quantize_rows``, ``scatter_set_rows`` and the
other row kernels of ``bench.harness.counts.ROW_KERNELS``): the bytes of the
M_s rows each call moves, at the chip's HBM bandwidth, over their summed
device time. A relayout copy or a padded tile is not counted as a byte."""
from bench.harness import counts, trace
from bench.harness.peaks import roofline_seconds

UNIT = "%"
MOVES = "rounds_per_s"


def read(ctx):
    summary = getattr(ctx, "summary", None)
    if summary is None or not hasattr(ctx, "num_select"):
        return None
    k = ctx.cell.config["num_factors"]
    bound = busy = 0.0
    for name in counts.ROW_KERNELS:
        calls, secs = trace.kernel_seconds(summary, [name])
        if calls:
            nbytes = counts.row_kernel_bytes(name, ctx.num_select, k)
            bound += calls * roofline_seconds(0.0, nbytes, ctx.device_kind)[0]
            busy += secs
    if busy <= 0:
        return None
    return 100.0 * bound / busy
