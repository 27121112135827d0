"""The plain references are in the tree twice (ROADMAP D12): the
program's tests read `distributed_reinforcement_learning_tpu/reference/`,
the benchmark reads `perfbench/references/`. Until a `benchmark` issue
gives them one owner, the two copies are byte-equal: an edit to one that
misses the other fails here. Reads both, edits neither.
"""

import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROGRAMS = os.path.join(ROOT, "distributed_reinforcement_learning_tpu", "reference")
BENCHMARKS = os.path.join(ROOT, "perfbench", "references")
NAMES = ["nemotron_h_moe.py", "smallthinker_moe.py", "lfm2_moe.py", "joyai_flash.py", "qwen3_next.py", "granite_hybrid.py",
         "ouro_looplm.py", "r2d2_atari.py"]


@pytest.mark.parametrize("name", NAMES)
def test_the_two_copies_of_a_reference_are_byte_equal(name):
    with open(os.path.join(PROGRAMS, name), "rb") as f:
        programs = f.read()
    with open(os.path.join(BENCHMARKS, name), "rb") as f:
        benchmarks = f.read()
    assert len(programs) > 1000
    assert programs == benchmarks, (
        f"{name}: reference/ and perfbench/references/ differ (ROADMAP D12)")
