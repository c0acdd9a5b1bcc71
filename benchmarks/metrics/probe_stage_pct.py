"""`probe_stage_pct` (%; layer: kernels; device trace): chip 0's busy
seconds that are not the hash kernel's, over the traced slice: what the
stage behind the kernel (a bulk list's bitmap lookup, survivor
compaction and exact verify) costs the device.  Nothing without a
trace or where the trace names no kernel.  Moves `cand_per_s`."""


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr["kernel_s"]:
        return None
    return 100.0 * (tr["busy_s"] - tr["kernel_s"]) / tr["window_s"]
