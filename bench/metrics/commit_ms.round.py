"""Device time per round of the commit: uplink encode and decode, sparse
Adam, the row scatters, reward feedback and the posterior update.

The leaf ops under the round's ``fl_commit`` scope, summed over the traced
window and divided by its rounds. Read from each op's name stack
(``bench/harness/scopes.py``), so it names the phase whichever op or kernel
does the work; None where no op carries the scope (a program built without
it, or served from a compile cache warmed by one)."""
from bench.harness import scopes

UNIT = "ms"
MOVES = "rounds_per_s"


def read(ctx):
    return scopes.per_round_ms(ctx, "fl_commit")
