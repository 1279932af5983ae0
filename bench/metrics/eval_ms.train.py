"""Device time of eval at each chunk boundary: the runs of the programs
launched inside the program's ``eval`` spans in the traced window (the
users' solve, the scores and the ranked metrics), per span. A program is
tied to the span by its launch on the host, not by when it ran
(``bench/harness/scopes.py``). None where the trace has no ``eval`` span."""
from bench.harness import scopes

UNIT = "ms"
MOVES = "rounds_per_s"


def read(ctx):
    s = scopes.for_ctx(ctx)
    return None if s is None else scopes.device_ms_per_span(s, "eval")
