"""Host milliseconds per round outside the benchmark's spans around
``FederatedTask.local_train`` and ``FederatedTask.evaluate``: planning,
aggregation dispatch and Python glue.  ``evaluate`` waits for the device, so
the time the host waits on training falls inside its span, not here."""
from bench import tracereduce

UNIT = "ms"


def read(view):
    ns, rounds = tracereduce.host_outside_ns(
        view.trace, "bench.round", ("bench.local_train", "bench.evaluate"))
    return ns / rounds / 1e6 if rounds else None
