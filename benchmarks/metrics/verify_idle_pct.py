"""`verify_idle_pct` (%; layer: host verify; program span): each chip's
idle seconds whose innermost `dprf:` station on the loop's thread is
`decode`, `verify`, `readback`, `reprobe`, `reprobe_wait` or `oracle`,
over the traced slice, as the mean over chips (`chip_spans.py`; the
way `device_idle_pct` counts): the device waiting on the host's
verification of what it found.  Nothing without a trace, or where the
program has no such stations.  Moves `cand_per_s`."""

import chip_spans


def read(obs):
    r = chip_spans.chip_spans(obs)
    if not r:
        return None
    idle = chip_spans.verify_idle_s(r)
    return 100.0 * sum(idle) / len(idle) / r["window_s"]
