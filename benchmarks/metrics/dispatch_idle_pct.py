"""`dispatch_idle_pct` (%; layer: fused dispatch; program span): chip
0's idle seconds under `dprf:lease`, `dprf:submit` and `dprf:complete`
over the traced slice (`span_reduce.py`): the host's work for each
unit between two dispatches (split and ledger, digit decode of the
base, argument transfer, enqueue, journal write) while the device has
nothing queued.  Moves `cand_per_s`."""

import span_reduce


def read(obs):
    return span_reduce.idle_pct(obs, ("lease", "submit", "complete"))
