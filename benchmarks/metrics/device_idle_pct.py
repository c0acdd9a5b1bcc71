"""`device_idle_pct` (%; layer: device; device trace): 1 - the union of
the intervals in which a program ran on the device, over the traced
slice, averaged over the chips.  Moves `cand_per_s`."""


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
