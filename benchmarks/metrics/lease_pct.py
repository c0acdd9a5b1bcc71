"""`lease_pct` (%; layer: lease / journal; host clock): the wrapper's
time inside the dispatcher's `lease()` and `complete()` over the
window.  Moves `cand_per_s`."""


def read(obs):
    seconds = obs["t_close"] - obs["t_open"]
    inside = (obs["host_seconds"].get("lease", 0.0)
              + obs["host_seconds"].get("complete", 0.0))
    return 100.0 * inside / seconds if seconds > 0 else None
