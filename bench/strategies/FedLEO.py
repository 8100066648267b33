"""Reference round of ``FedLEO``: one group per orbit, in the order the round
took them (its ``events["planes"]``).

``SCHEDULE_FAULT`` is the strategy's arguments for a worse schedule, to show
that the schedule check catches one: the program's own ablation, in which
the next satellite to see the station is the sink, whether its window fits
the exchange or not."""
from bench import reference as ref

SCHEDULE_FAULT = {"sink_policy": "first_visitor"}


def reference_round(rt, params, treedef, events, sats_per_plane):
    groups = [[g["plane"] * sats_per_plane + s for s in range(sats_per_plane)]
              for g in events["planes"]]
    return ref.sync_round(rt, params, treedef, groups)
