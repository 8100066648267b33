"""Device milliseconds per sequential SGD step of local training: the
device time of the vmapped local-train program's events over the steps its
calls ran (epochs x batches per call, summed over calls)."""
from bench import tracereduce

UNIT = "ms"
PROGRAM = "_local_train_one"


def read(view):
    ns, n = tracereduce.events_ns(view.trace, tracereduce.MODULES_LINE, PROGRAM)
    steps = view.counters.train_steps
    return ns / steps / 1e6 if n and steps else None
