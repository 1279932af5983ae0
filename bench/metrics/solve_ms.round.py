"""Device time per round of the cohort solve: each user's ridge solve
for p_i against the decoded Q* (the batched (Theta, K, K) ``custom-call``
and what feeds it).

The leaf ops under the round's ``fl_solve`` scope, summed over the traced
window and divided by its rounds. Read from each op's name stack
(``bench/harness/scopes.py``), so it names the phase whichever op or kernel
does the work; None where no op carries the scope (a program built without
it, or served from a compile cache warmed by one)."""
from bench.harness import scopes

UNIT = "ms"
MOVES = "rounds_per_s"


def read(ctx):
    return scopes.per_round_ms(ctx, "fl_solve")
