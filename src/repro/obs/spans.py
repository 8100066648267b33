"""Wall-time spans of the FL round, stamped by the JAX profiler.

``span(name, **stats)`` names one stretch of host work inside the round
(``repro.round``, ``repro.group``, ``repro.plan``, ``repro.commit``,
``repro.local_train``, ``repro.aggregate``, ``repro.evaluate``,
``repro.wait``) with integer stats such as the steps a call trains or
the bytes an aggregation reads.  It reads no clock itself: the profiler
stamps the events, on the same clock as the device's, so a span lines
up with the device work it dispatched and the idle gaps around it.
Unlike ``TraceRecorder`` (simulated seconds, schedule semantics), these
spans measure the program itself.

A span changes no result, and costs next to nothing while no profiler
runs.  To see the spans, wrap rounds in the profiler::

    with jax.profiler.trace("fl-trace"):
        strategy.run_round(t)

then open the directory in TensorBoard's profile plugin or Perfetto, or
read the ``.xplane.pb`` with ``jax.profiler.ProfileData``: the spans are
events on a ``/host:CPU`` plane, nested on the calling thread's line,
with their stats as event stats.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

if TYPE_CHECKING:
    from jax.profiler import TraceAnnotation

SPAN_PREFIX = "repro."


def span(name: str, **stats: int) -> TraceAnnotation:
    """The context manager of one program span, ``repro.<name>``."""
    # imported here: the planners import repro.obs and run without JAX
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(SPAN_PREFIX + name, **stats)
