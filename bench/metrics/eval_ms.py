"""Device milliseconds per round of the evaluation program."""
from bench import tracereduce

UNIT = "ms"
PROGRAM = "jit__eval"


def read(view):
    ns, n = tracereduce.events_ns(view.trace, tracereduce.MODULES_LINE, PROGRAM)
    return ns / view.window.rounds / 1e6 if n else None
