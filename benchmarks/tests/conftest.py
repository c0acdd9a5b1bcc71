"""The benchmark's own tests: run by hand from the repo's root,

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

(tier-1 collects `tests/` only).  They drive the harness on the CPU at
a tiny size: kernels interpreted (`DPRF_PALLAS=1`), four virtual
devices for the mesh.  A CPU run shows results and counts, never a
rate.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
os.environ["DPRF_PALLAS"] = "1"
os.environ["DPRF_PALLAS_SUB"] = "32"

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = os.path.join(HERE, "data")
