"""Whole-round share of the chip's bf16 peak: the algorithm's operations per
round (``bench.harness.counts.round_ops``: selection, wire, cohort solve,
gradient, commit, rewards) times the window's committed rounds per second."""
from bench.harness.peaks import peaks_for

UNIT = "%"
MOVES = "rounds_per_s"


def read(ctx):
    flops = getattr(ctx, "round_flops", None)
    if not flops:
        return None
    peak = peaks_for(ctx.device_kind)["bf16_flops"]
    return 100.0 * flops * ctx.rounds_per_s / peak
