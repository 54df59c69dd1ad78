"""The benchmark's own tests run on the CPU: four virtual devices, so
that the mesh cell can be rehearsed. (The repo's tier-1 suite under
``tests/`` does not collect this directory.)"""

import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 4)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT, os.path.join(BENCH, "reference")):
    if p not in sys.path:
        sys.path.insert(0, p)
