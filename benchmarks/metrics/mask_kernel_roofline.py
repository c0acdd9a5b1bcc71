"""`mask_kernel_roofline` (%; layer: kernels; device trace): the hash
kernel's share of the chip's measured integer peak:

    calls x lanes a call x operations a candidate (`work.py`)
    -----------------------------------------------------------
        kernel seconds of those calls x peak (`peaks.json`)

over the kernel calls that lie wholly inside the traced slice, on one
chip (averaged over the chips).  A call sweeps `--batch` lanes on each
chip: that is what the flag means, and the one thing read from the
configuration.  The trace names the kernel only as a custom call, so
the count is held against the harness's own ledger: calls x lanes may
not pass the candidates of the units that were in flight during the
slice by half.  Where they do, the programs hold more than one custom
call a batch, the numerator would count the work twice, and that is an
error.
Bound by operations: the kernel moves a byte a candidate.  A device
kind that `peaks.json` does not hold is an error.  Moves
`cand_per_s`."""

import work


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr["kernel_calls"] or not tr["kernel_whole_s"]:
        return None
    lanes = tr["kernel_calls"] * obs["cfg"]["flags"]["batch"]
    t0 = obs["t_close"] - tr["window_s"]
    in_flight = sum(n for _, n, _, t in obs["units"] + obs["tail_units"]
                    if t is None or t > t0)
    if lanes * obs["n_devices"] > 1.5 * in_flight:
        raise RuntimeError(
            f"mask_kernel_roofline: {tr['kernel_calls']} kernel calls a "
            f"chip x {obs['cfg']['flags']['batch']} lanes is more than the "
            f"{in_flight} candidates in flight during the slice: more "
            "than one custom call a batch")
    rate = lanes / tr["kernel_whole_s"]
    return 100.0 * rate * work.ops_of(obs) / work.peak_int32(
        obs["device_kind"])
