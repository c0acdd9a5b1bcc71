#!/usr/bin/env python
"""Driver benchmark entry: prints ONE JSON line.

One process, one cell: BASELINE config 1 (MD5, one hash, mask
``?l?l?l?l?l?l``) through the production worker path
(``dprf_tpu.bench.run_config``: engine factory -> kernel worker ->
submit/resolve pipeline), at the config's stated batch.

No chip, no number: when ``jax.devices()[0].platform`` is not a TPU the
script prints an error line without a value and exits non-zero.  It
starts no child process (a chip belongs to one process at a time), and
a result always names the device it ran on.  The benchmark proper --
every cell, traced runs, regression bounds -- is ROADMAP item S1 and
builds on this.
"""

import json
import os
import sys

#: config 1's stated device batch (BASELINE.json) and worker batches
#: per WorkUnit: enough that a unit goes out as fused dispatches
BATCH = 1 << 22
UNIT_STRIDES = 64
SECONDS = 15.0


def main() -> int:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax
    dev = jax.devices()[0]
    device = {"device": dev.platform, "device_kind": dev.device_kind,
              "device_count": len(jax.devices())}
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, **device,
                          "error": "no TPU: bench.py measures the chip "
                          "and reports nothing from another platform"}))
        return 1
    from dprf_tpu.bench import run_config
    from dprf_tpu.runtime.worker import PallasMaskWorker
    res = run_config(1, device="jax", seconds=SECONDS, batch=BATCH,
                     unit_strides=UNIT_STRIDES)
    if res.get("worker") != PallasMaskWorker.__name__ \
            or res.get("interpret") is not False:
        print(json.dumps({"ok": False, **device,
                          "error": "config 1 did not run on the "
                          "compiled kernel worker",
                          "worker": res.get("worker"),
                          "interpret": res.get("interpret")}))
        return 1
    print(json.dumps({**res, **device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
