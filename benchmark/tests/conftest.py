"""benchmark/tests run by hand on the CPU: ``python -m pytest benchmark/tests -q``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.run import force_cpu_devices  # noqa: E402

# four virtual CPU devices, for the four-chip cell's rehearsal in process
force_cpu_devices(4)
