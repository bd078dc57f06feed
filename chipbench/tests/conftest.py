"""These tests are run by hand (``JAX_PLATFORMS=cpu python -m pytest
chipbench/tests``), not by tier-1. They run on the CPU whatever the shell
exported: four virtual devices for the four-chip cell's rehearsal. Nothing
here touches a TPU, at import or later."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("SXT_LOG_LEVEL", "warning")
