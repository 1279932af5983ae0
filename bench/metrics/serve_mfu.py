"""Whole-request share of the chip's bf16 peak: the useful scoring work of
every served request, 2 B M K with B its real users (padding is no work),
over the summed service times of those requests."""
from bench.harness.peaks import peaks_for

UNIT = "%"
MOVES = "serve_p95_ms"


def read(ctx):
    if not getattr(ctx, "service_s", 0.0):
        return None
    peak = peaks_for(ctx.device_kind)["bf16_flops"]
    return 100.0 * ctx.useful_ops / (ctx.service_s * peak)
