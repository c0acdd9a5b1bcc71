"""`kernel_pct` (%; layer: kernels; device trace): device time of the
hash kernel's events over the traced slice, averaged over the chips.
Moves `cand_per_s`."""


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr["kernel_s"]:
        return None
    return 100.0 * tr["kernel_s"] / tr["window_s"]
