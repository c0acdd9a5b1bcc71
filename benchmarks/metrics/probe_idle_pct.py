"""`probe_idle_pct` (%; layer: sweep loop; program span): chip 0's idle
seconds whose innermost station is `dprf:probe`, the phase sampler's
synced per-batch unit, over the traced slice (`span_reduce.py`); a
`dprf:decode` nested in a probe is `decode`'s.  The share of the
window that leaving the probed units out would win back.  Moves
`cand_per_s`."""

import span_reduce


def read(obs):
    return span_reduce.idle_pct(obs, ("probe",))
