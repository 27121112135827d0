"""Test configuration: run everything on a simulated 8-device CPU mesh.

The tests run on the CPU (`JAX_PLATFORMS=cpu`), where multi-chip
sharding paths see 8 virtual devices; the chip is reached only through
`chip_smoke.py` (see .claude/skills/verify/SKILL.md). The persistent
compilation cache stays off, here and in every process a test starts:
tests must not depend on what an earlier run left on disk, and the
ahead-of-time TPU compiles of tests/test_tpu_compile.py would write
entries that no process without a chip can read back.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute drills excluded from tier-1 (-m 'not slow')")
