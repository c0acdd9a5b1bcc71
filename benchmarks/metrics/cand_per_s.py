"""`cand_per_s` (Gcand/s, end to end, host clock): every candidate of
every unit leased and completed in the window, over the whole window,
from its opening to the last `complete()` of the drain."""


def read(obs):
    seconds = obs["t_close"] - obs["t_open"]
    done = sum(n for _, n, _, t in obs["units"] if t is not None)
    if seconds <= 0 or not done:
        return None
    return done / seconds / 1e9
