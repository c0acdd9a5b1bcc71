"""`survivors_per_mcand` (count; layer: host verify; program counter):
lanes the list's bitmap passed, a million candidates: the job's own
`ran verify=survivors:<n>` over the candidates of its dispatches
(`dispatch=`: a fused dispatch is a unit's batches; warm and judged
units are the line's too).  The table's analytic false-positive rate
times 10^6 is the yardstick.  Nothing on a program without the
counter.  Moves `cand_per_s`."""


def read(obs):
    ran = obs["log"].get("ran") or {}
    counts = dict(f.split(":", 1) for f in ran.get("verify", "").split(",")
                  if ":" in f)
    shapes = obs["log"].get("shapes") or {}
    if "survivors" not in counts or not shapes:
        return None
    flags = obs["cfg"]["flags"]
    lanes = flags["batch"] * obs["cell"]["chips"]
    per_unit = flags["unit_size"] // lanes
    batches = sum(n if k in ("probe", "batch") else n * per_unit
                  for k, n in shapes.items())
    return 1e6 * int(counts["survivors"]) / (batches * lanes)
