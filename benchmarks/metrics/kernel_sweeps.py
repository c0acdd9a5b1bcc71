"""`kernel_sweeps` (ratio; layer: kernels; device trace): how often the
hash kernel swept a candidate of the traced slice:

    kernel calls a chip x lanes a call x chips   (`work.slice_lanes`)
    ----------------------------------------------------------------
      candidates of the units in flight during the slice

1 is the target: every candidate hashed once.  It reads a little under
it, since the units at the slice's edges are counted whole and their
calls only as far as they lie inside it: 0.8 to 1.0 in a slice of ten
units, 0.97 to 1.0 in one of a hundred.  `better` says `lower` because
what a program can do wrong is to sweep again: nearly 2 where it
sweeps most of its windows a second time (the sharded worker's redrive
of a window whose hit buffer overflowed).  A reading under about 0.8
is no gain but a fault of this reader or of the ledger it divides by
(it read 0.18 once, with a one-target job's tail counted as in
flight).  Over `work.MAX_SWEEPS` nothing is read: the entry driver's
`KERNEL_EVENT` then matches more than the hash kernel (three events a
batch, once, PR 29), `kernel_pct` and `mask_kernel_roofline` would
count those events too, and `slice_lanes` raises.  Nothing where the
trace holds no kernel call.  Moves `cand_per_s`."""

import work


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr["kernel_calls"]:
        return None
    swept, in_flight = work.slice_lanes(obs)
    return swept / in_flight if in_flight else None
