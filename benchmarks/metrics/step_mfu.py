"""`step_mfu` (%; layer: device; host clock over `work.py`): the whole
window's share of the chips' measured integer peak: every candidate
completed in the window x operations a candidate, over the window's
seconds x chips x peak.  What a kernel's roofline cannot say once the
kernel is off the path.  Moves `cand_per_s`."""

import work


def read(obs):
    seconds = obs["t_close"] - obs["t_open"]
    done = sum(n for _, n, _, t in obs["units"] if t is not None)
    if seconds <= 0 or not done or not obs.get("device_kind"):
        return None
    peak = obs["n_devices"] * work.peak_int32(obs["device_kind"])
    return 100.0 * done * work.ops_of(obs) / seconds / peak
