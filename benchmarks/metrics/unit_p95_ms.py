"""`unit_p95_ms` (ms; layer: sweep loop; host clock): the 95th
percentile of the window's lease-to-complete times of a unit.  Nothing
under 200 units: the tail wants ten samples beyond it.  Moves
`cand_per_s`."""

import statistics


def read(obs):
    times = [t - t0 for _, _, t0, t in obs["units"] if t is not None]
    if len(times) < 200:
        return None
    return 1e3 * statistics.quantiles(times, n=20)[-1]
