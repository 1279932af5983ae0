"""Programs launched per chunk boundary to publish the model: the median
count of program launches on the host inside the program's ``publish``
spans in the traced window. Each is a dispatch the boundary waits on;
encoding the table op by op launches one per op. None where the trace has
no ``publish`` span."""
from bench.harness import scopes

UNIT = "programs"
MOVES = "rounds_per_s"


def read(ctx):
    s = scopes.for_ctx(ctx)
    return None if s is None else scopes.launches_per_span(s, "publish")
