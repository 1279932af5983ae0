"""Share of its roofline that the fused dequant-score-top-N kernel reaches in
the traced serving window: for each call, the least time the chip needs for
its bucket's operations and bytes (``bench.harness.counts.score_ops`` and
``score_bytes``; the bucket is read from the call's result shape), summed,
over the calls' device time."""
from bench.harness import counts, trace
from bench.harness.peaks import roofline_seconds

UNIT = "%"
MOVES = "serve_p95_ms"
KERNELS = ("quant_topn", "quant4_topn", "dense_topn")


def read(ctx):
    summary = getattr(ctx, "summary", None)
    if summary is None or not hasattr(ctx, "num_items"):
        return None
    calls = [op for op in summary.kernel_calls if op.name in KERNELS]
    busy = sum(op.end - op.start for op in calls)
    if busy <= 0:
        return None
    m, k, n = ctx.num_items, ctx.k, ctx.top_n
    bound = 0.0
    for op in calls:
        b = trace.result_shape(op)[1][0]
        bound += roofline_seconds(counts.score_ops(b, m, k),
                                  counts.score_bytes(b, m, k, n),
                                  ctx.device_kind)[0]
    return 100.0 * bound / busy
