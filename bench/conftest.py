"""Benchmark CPU tests: the emulated devices the repository's tests use, so
the four-chip path runs on a (1, 4) mesh of CPU devices."""

import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
