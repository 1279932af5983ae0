"""Share of its roofline that the gather of the cohort's payload columns
reaches: the (Theta, M_s) float32 block X[cohort][:, selected] of the dense
interaction matrix, which each round's solve and gradient read. It is XLA's
work, not a Pallas kernel: every leaf op outside the kernels whose result
holds exactly Theta x M_s values (the gather, its index arithmetic and its
reshapes), the same number of them in every traced round (where the count
is not a whole multiple of the rounds, nothing is read). The least time is
that block read once and written once at HBM bandwidth
(``counts.cohort_gather_bytes``)."""
import math

from bench.harness import counts, trace
from bench.harness.peaks import roofline_seconds

UNIT = "%"
MOVES = "rounds_per_s"


def read(ctx):
    summary = getattr(ctx, "summary", None)
    rounds = getattr(ctx, "traced_rounds", 0)
    if summary is None or not hasattr(ctx, "num_select") or not rounds:
        return None
    theta, m_s = ctx.cell.config["theta"], ctx.num_select
    calls = [op for op in summary.leaves
             if "tpu_custom_call" not in op.text
             and math.prod(trace.result_shape(op)[1] or [0]) == theta * m_s]
    busy = sum(op.end - op.start for op in calls) / summary.chips
    if not calls or len(calls) % (rounds * summary.chips) or busy <= 0:
        return None
    bound, _ = roofline_seconds(0.0, counts.cohort_gather_bytes(theta, m_s),
                                ctx.device_kind)
    return 100.0 * rounds * bound / busy
