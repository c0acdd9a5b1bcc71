"""Integer operations one candidate costs: with the candidates hashed,
the numerator of `mask_kernel_roofline` (a traced slice's,
`slice_lanes`) and of `step_mfu` (the whole window's).

The count itself is the engine's: `engines/<engine>.py`
`ops_per_candidate`, with its source (the RFC or FIPS text the steps
are read from) and its own folding written beside it; `ops_of` finds it
by the configuration's `engine`.  This file holds the rules every such
count keeps, the two helpers the one-block hashes share, the slice's
lanes (`slice_lanes`, `MAX_SWEEPS`) and the table of peaks.

The rules.  The count is of what no correct implementation can avoid,
so that the share reads the same work whatever implements the kernel
and cannot honestly pass 100 %.  One operation is one 32-bit add, and,
or, xor, not-combined-with-another-op, shift or ROTATE on one lane.
Taken as given, in the kernel's favour:

- a rotate is ONE operation (the v5e vector unit has none and pays a
  shift, a shift and an or: that cost is the kernel's, and shows as a
  lower share);
- message words that are the same for every candidate are folded into
  the round constant (`M[k] + K[i]` is one constant), an all-zero word
  under a zero constant costs nothing, and what is computed from
  constants alone costs nothing;
- the boolean functions at their cheapest known forms;
- a single target is met in the middle where the hash allows: the
  trailing steps whose message word is constant are undone once on the
  target and never run per candidate (hashcat does this), and the add
  of the initial state is folded into the target; a list of targets
  cannot be, and pays every step;
- index -> candidate decode is not counted (an implementation can step
  the odometer instead of dividing), nor the compare, the probe bitmap
  or the hit reduction.

`peak_int32` is the other half of the yardstick: the table of measured
peaks, `peaks.json`, keyed by `device_kind`.
"""

import json
import os

import engines


def varying_words(n_bytes):
    """Message words of a Merkle-Damgard block that differ between
    candidates: the password's bytes and the 0x80 that follows them."""
    return set(range((n_bytes + 1 + 3) // 4))


def trailing_constant(order, varying):
    """How many of the last steps (`order`: the message word each step
    reads) read a constant word: what a single target undoes once."""
    n = 0
    for k in reversed(order):
        if k in varying:
            break
        n += 1
    return n


def ops_of(obs):
    """Operations a candidate of the run's job costs: the count of the
    configuration's engine, at the mask's length in characters."""
    cfg = obs["cfg"]
    mask_len = len(obs["plan"].plants[0].plain)
    return engines.load(cfg["engine"]).ops_per_candidate(mask_len, cfg)


#: `slice_lanes` refuses a slice whose kernel calls swept more than
#: this many times the candidates in flight: no program does (a window
#: whose hit buffer overflowed is swept a second time and never a
#: third, so a job reads at most 2), and an entry driver's
#: `KERNEL_EVENT` that matches other events beside the hash kernel does
#: (three a batch read 3.2 once, PR 29)
MAX_SWEEPS = 2.5


def slice_lanes(obs):
    """(swept, in_flight) of a traced run's slice, both over all chips:
    the lanes its kernel calls swept (the calls that lie wholly inside
    the slice, a chip, x `--batch` lanes a call and chip: that is what
    the flag means, and the one thing read from the configuration, x
    chips), and the candidates of the units in flight during the slice
    by the harness's own ledger: every unit leased before the slice
    ended (it ends with the window: a one-target job's tail is leased
    after it) and not completed before it began, each counted whole.
    `mask_kernel_roofline`'s numerator is the first, as the kernel was
    called; `kernel_sweeps` is the one over the other.  Over
    `MAX_SWEEPS` the calls were miscounted and every `kernels` metric
    with them: an error, not a number."""
    tr = obs["trace"]
    swept = (tr["kernel_calls"] * obs["cfg"]["flags"]["batch"]
             * obs["n_devices"])
    t1 = obs["t_close"]
    t0 = t1 - tr["window_s"]
    in_flight = sum(n for _, n, leased, done
                    in obs["units"] + obs["tail_units"]
                    if leased < t1 and (done is None or done > t0))
    if swept > MAX_SWEEPS * in_flight:
        raise RuntimeError(
            f"{tr['kernel_calls']} kernel calls a chip swept {swept} "
            f"lanes where {in_flight} candidates were in flight: more "
            f"than {MAX_SWEEPS} sweeps of each, which no program makes; "
            f"the entry driver's KERNEL_EVENT matches more than the "
            f"hash kernel")
    return swept, in_flight


def peak_int32(device_kind):
    """The measured int32 op/s of one chip of that kind; a kind the
    table does not hold is an error, not a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as fh:
        return json.load(fh)["peaks"][device_kind]["int32_ops_per_s"]
