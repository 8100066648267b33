"""Local training's share of the chip's bf16 peak: forward + backward FLOPs
of the samples trained in the window (3 x the configuration's forward count
per sample), over the device time of the local-train program's events.  The
program's float32 runs one bfloat16 MXU pass, so the bf16 peak is the one it
can reach."""
from bench import tracereduce

UNIT = "%"
PROGRAM = "_local_train_one"


def read(view):
    ns, n = tracereduce.events_ns(view.trace, tracereduce.MODULES_LINE, PROGRAM)
    samples = view.counters.train_samples
    if not n or not samples:
        return None
    flops = 3 * view.flops_per_sample * samples
    return 100.0 * flops / (ns / 1e9) / view.peak["bf16_flops_per_s"]
