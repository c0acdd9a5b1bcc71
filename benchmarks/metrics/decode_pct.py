"""`decode_pct` (%; layer: host verify; program span): the seconds of
the loop's thread inside `dprf:decode` and `dprf:verify`, with
everything nested in them (the harness's `bench:oracle` too), over the
traced slice (`span_reduce.py`): the whole host verification path, hit
readback, `_batch_hits`, oracle calls, tile rescans, potfile and
journal hit lines.  `decode_pct - oracle_pct` is what verification
costs beside the oracle's hashing.  Moves `cand_per_s`."""

import span_reduce


def read(obs):
    r = span_reduce.spans(obs)
    return 100.0 * r["verify_path_s"] / r["window_s"] if r else None
