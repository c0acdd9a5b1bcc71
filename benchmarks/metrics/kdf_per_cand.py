"""`kdf_per_cand` (ratio; layer: kernels; program counter): key
derivations the program dispatched a candidate of its job: the job's
own `ran kdf=evals:<n>` (PBKDF2 evaluations: one a valid lane and
target swept) over the candidates of every unit the job leased, warm,
window and tail (every leased unit is submitted, so each was
dispatched; the units judged after the job come after its `ran`
line).  1.0 is the target for a one-target job.  A unit swept twice
reads above it, and so does a PMK computed once a target where
targets share an ESSID.  The denominator is the harness's ledger and
not the line's `dispatch=` count as in `survivors_per_mcand`: a
dispatch is one target's batch, so its lanes count every target's PMK
and that ratio would read 1 by construction.  Nothing on a program
without the counter.  Moves `cand_per_s`."""


def read(obs):
    ran = obs["log"].get("ran") or {}
    counts = dict(f.split(":", 1) for f in ran.get("kdf", "").split(",")
                  if ":" in f)
    leased = sum(n for _, n, _, _ in
                 obs["warm_units"] + obs["units"] + obs["tail_units"])
    if "evals" not in counts or not leased:
        return None
    return int(counts["evals"]) / leased
