"""Tests of the benchmark itself: `python -m pytest bench/tests`.

They run on the CPU. Four host devices stand in for the four-chip
mesh; the flag has to be set before JAX starts.
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
