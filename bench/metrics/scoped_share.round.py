"""Share of the chunk program's device time that the round's phase scopes
name: the leaf ops under any ``fl_*`` scope over all leaf ops of the runs
of the program that the ``train_chunk`` spans launched, in the traced
window. What is left is the scan's own copies of the carried state. None
where no op carries a scope."""
from bench.harness import scopes

UNIT = "%"
MOVES = "rounds_per_s"


def read(ctx):
    s = scopes.for_ctx(ctx)
    if s is None or not s.scoped:
        return None
    ops = scopes.chunk_ops(s)
    total = s.seconds(ops)
    if total <= 0:
        return None
    return 100.0 * s.seconds([o for o in ops if scopes.in_phase(o)]) / total
