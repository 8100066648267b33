"""Share of the traced window in which no op ran on the device."""
from bench import tracereduce

UNIT = "%"


def read(view):
    window = tracereduce.window_ns(view.trace)
    return 100.0 * (1.0 - tracereduce.busy_ns(view.trace) / window)
