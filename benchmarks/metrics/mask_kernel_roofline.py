"""`mask_kernel_roofline` (%; layer: kernels; device trace): the hash
kernel's share of the chip's measured integer peak:

    calls x lanes a call x operations a candidate (`work.py`)
    -----------------------------------------------------------
        kernel seconds of those calls x peak (`peaks.json`)

over the kernel calls that lie wholly inside the traced slice, on one
chip (averaged over the chips).  A call sweeps `--batch` lanes on each
chip (`work.slice_lanes`).  It is the kernel's share as the kernel was
called: a program that sweeps a window a second time calls the same
kernel twice at the same share, and it is `kernel_sweeps`, beside
this, that reads the second pass.  The trace names the kernel only as
a custom call, so the count is held against the harness's own ledger:
where the calls swept more than `work.MAX_SWEEPS` times the candidates
of the units in flight during the slice, which no program does, the
events counted are not the hash kernel's alone and `slice_lanes`
raises.
Bound by operations: the kernel moves a byte a candidate.  A device
kind that `peaks.json` does not hold is an error.  Moves
`cand_per_s`."""

import work


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr["kernel_calls"] or not tr["kernel_whole_s"]:
        return None
    swept, _ = work.slice_lanes(obs)
    rate = swept / obs["n_devices"] / tr["kernel_whole_s"]
    return 100.0 * rate * work.ops_of(obs) / work.peak_int32(
        obs["device_kind"])
