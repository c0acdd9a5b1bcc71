"""`reprobe_wait_pct` (%; layer: host verify; program span): the loop
thread's self seconds inside `dprf:reprobe_wait` over the traced slice
(`chip_spans.py`): the host blocked in `_tile_hits` until the next
unit's program and the collided tiles' re-probes queued behind it have
run.  Nothing without a trace, or where the program has no such
station.  Moves `cand_per_s`."""

import chip_spans


def read(obs):
    r = chip_spans.chip_spans(obs)
    if not r:
        return None
    return 100.0 * r["self_s"].get("dprf:reprobe_wait", 0.0) / r["window_s"]
