"""`probe_units_pct` (%; layer: sweep loop; program counter): of the
job's batches, the share the phase sampler swept per-batch and synced,
from the job's own `ran dispatch=probe:N,loop:M,batch:K` line (a fused
dispatch of a unit counts as the unit's batches).  Counts the warm
units too: the line is the job's.  Moves `cand_per_s`."""


def read(obs):
    shapes = obs["log"].get("shapes")
    if not shapes:
        return None
    flags = obs["cfg"]["flags"]
    per_unit = flags["unit_size"] // (flags["batch"] * obs["cell"]["chips"])
    fused = sum(n for k, n in shapes.items()
                if k not in ("probe", "batch")) * per_unit
    total = fused + shapes.get("probe", 0) + shapes.get("batch", 0)
    return 100.0 * shapes.get("probe", 0) / total if total else None
