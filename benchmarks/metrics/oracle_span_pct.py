"""`oracle_span_pct` (%; layer: host verify; program span): the loop
thread's seconds inside `dprf:oracle`, with what nests in it (the
harness's `bench:oracle`), over the traced slice (`chip_spans.py`):
the program's own account of the CPU oracle's hashing, which
`oracle_pct` times from outside through the harness's proxy.  Nothing
without a trace, or where the program has no such station.  Moves
`cand_per_s`."""

import chip_spans


def read(obs):
    r = chip_spans.chip_spans(obs)
    if not r:
        return None
    return 100.0 * r["inclusive_s"].get("dprf:oracle", 0.0) / r["window_s"]
