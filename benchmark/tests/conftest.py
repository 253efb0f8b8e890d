"""The benchmark's own tests run on the CPU, on four virtual devices
(the mesh cell's fault test needs them): `python -m pytest benchmark/tests -q`
from the root of the repo. They are not part of the repo's tier-1 run."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=4").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

