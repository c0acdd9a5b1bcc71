"""`idle_unnamed_pct` (%; layer: device; program span): of chip 0's
idle seconds in the traced slice, the share during which the thread
that runs the job's loop had no `dprf:` station open
(`span_reduce.py`).  What the program's stations leave unexplained:
the smaller, the more of `device_idle_pct` has a name.  Nothing
without a trace or where the program has no stations.  Moves
`cand_per_s`."""

import span_reduce


def read(obs):
    r = span_reduce.spans(obs)
    if not r or not r["idle_s"]:
        return None
    return 100.0 * r["idle_by_station_s"].get("unnamed", 0.0) / r["idle_s"]
