"""Device milliseconds per round of the aggregation program: the XLA module
``aggregate_pytree`` holds the concatenation, the Pallas ``aggregate_flat``
kernel and the per-leaf slices of one aggregation, at the sinks and at the
station.  A program that aggregates eagerly has no such module, and reads
nothing."""
from bench import tracereduce

UNIT = "ms"
PROGRAM = "aggregate_pytree"


def read(view):
    ns, n = tracereduce.events_ns(view.trace, tracereduce.MODULES_LINE, PROGRAM)
    return ns / view.window.rounds / 1e6 if n else None
