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


# Seconds of the files that take a minute and more (the driver's run at PR
# 52's tree, /root/TESTS_LAST_RUN.json's junit file; PR 53's own file as
# read there). The driver runs six workers with `--dist loadfile`, which
# hands a worker the next FILE in collection order when it falls free: in
# alphabetical order `test_tpu_compile.py` (677 s) started last of the long
# ones and the run ended when it did, 126 s after the six workers' mean.
# Longest first, the same cases pack to the mean (ISSUE 53, Satellite 1).
# No case is added, dropped or changed by this: only the order of FILES.
LONGEST_FIRST = {
    "test_tpu_compile.py": 677, "test_joyai_flash.py": 605, "test_qwen3_next.py": 534,
    "test_fused_setup.py": 336, "test_lfm2_moe.py": 278, "test_e2e.py": 243,
    "test_ximpala.py": 240, "test_xformer.py": 300, "test_anakin_r2d2.py": 188,
    "test_ouro_looplm.py": 169, "test_smallthinker_moe.py": 169,
    "test_granite_hybrid.py": 160, "test_checkpoint.py": 144, "test_scopes.py": 140,
    "test_nemotron_h_moe.py": 125, "test_r2d2_atari.py": 118, "test_fastpath.py": 112,
    "test_launch.py": 112, "test_sequence.py": 102, "test_chip_smoke.py": 99,
    "test_pallas.py": 93, "test_breakout_jax.py": 91, "test_multihost.py": 84,
    "test_expert_share.py": 140, "test_impala_time_major.py": 69,
}


def pytest_collection_modifyitems(items):
    """Whole files in the order of `LONGEST_FIRST` (a stable sort: a file's
    cases keep their order, the other files theirs behind them)."""
    items.sort(key=lambda item: -LONGEST_FIRST.get(
        os.path.basename(str(item.fspath)), 0))
