"""`oracle_pct` (%; layer: host verify; host clock): the proxy's time
inside the CPU oracle's `hash_batch` and `verify` over the window.
Nothing where the window never called the oracle (a one-target job
with no hit).  Moves `cand_per_s`."""


def read(obs):
    seconds = obs["t_close"] - obs["t_open"]
    if seconds <= 0 or not obs["host_counts"].get("oracle"):
        return None
    return 100.0 * obs["host_seconds"]["oracle"] / seconds
