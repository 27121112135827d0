"""chip_smoke.py off the chip: it must refuse, and its phases must run.

On the CPU the script has to exit non-zero with `"ok": false` — it
never trains on the CPU and reports ok. With its two test arguments
(`--config`, a file whose `impala` / `apex` / `r2d2_pixel` sections are
cut to CartPole size, and `--expect-platform cpu`) the same phases run
through the same entry points, which is the first of the three
rehearsals to make before sending the script to the chip
(.claude/skills/verify/SKILL.md).
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _run(args, cwd=REPO, script=SMOKE, env=None, timeout=300):
    proc = subprocess.run(
        [sys.executable, str(script), *args], cwd=cwd, timeout=timeout,
        capture_output=True, text=True,
        # XLA_FLAGS: conftest's 8 virtual devices are not inherited — the
        # script reports the devices it ran on, and one is expected.
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
             **(env or {})})
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert lines, proc.stdout + proc.stderr[-2000:]
    return proc, lines


@pytest.fixture
def tiny_config(tmp_path):
    """The three sections chip_smoke.py drives, at CartPole size. The
    IMPALA batch is wide enough that the cluster phase trains for about
    a second, so the actors' stats lines show the versions they pulled."""
    full = json.loads((REPO / "config.json").read_text())
    impala = {**full["impala_cartpole"], "batch_size": 64,
              "envs_per_actor": 4, "queue_size": 128, "lstm_size": 32}
    apex = {**full["apex"], "num_actors": 1, "env": ["CartPole-v0"],
            "available_action": [2], "model_input": [4], "model_output": 2,
            "envs_per_actor": 4}
    r2d2 = {**full["r2d2"], "lstm_size": 32}
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(
        {"impala": impala, "apex": apex, "r2d2_pixel": r2d2}))
    return str(path)


def test_refuses_to_run_without_a_tpu():
    proc, lines = _run([])
    assert proc.returncode != 0
    assert lines[-1]["ok"] is False
    assert "tpu" in lines[-1]["error"]
    # Refused at the first phase's device check: nothing trained.
    assert not any(ln.get("phase") for ln in lines), lines


def test_fails_in_a_directory_without_the_repo(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    proc, lines = _run([], cwd=tmp_path, script=alone)
    assert proc.returncode != 0
    assert lines[-1]["ok"] is False


def test_every_phase_runs_when_steered_to_the_cpu(tiny_config):
    proc, lines = _run(["--config", tiny_config, "--expect-platform", "cpu"])
    assert proc.returncode == 0, proc.stdout + proc.stderr[-3000:]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert list(phases) == ["local", "cluster", "anakin", "apex", "r2d2_pixel"]
    for name, ln in phases.items():
        assert ln["ok"] and ln["seconds"] > 0 and ln["setup_s"] > 0, ln
        assert ln["loss"] == ln["loss"], ln  # not NaN
        assert ln["device"]["platform"] == "cpu"
    assert phases["local"]["data_plane"] == "native"
    assert min(phases["cluster"]["actor_weight_versions"].values()) >= 1
    # The CPU resolves `auto` to the lax.scan reference: no kernel may
    # be claimed here (on the chip the script REQUIRES two).
    assert phases["local"]["kernels"] == []


@pytest.mark.slow
def test_chips_4_runs_only_the_mesh_path(tiny_config):
    """The second rehearsal: the four-chip path on four virtual devices."""
    proc, lines = _run(
        ["--chips", "4", "--config", tiny_config, "--expect-platform", "cpu"],
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert proc.returncode == 0, proc.stdout + proc.stderr[-3000:]
    assert lines[-1]["ok"] and lines[-1]["device"]["count"] == 4
    assert [ln["phase"] for ln in lines[:-1]] == ["sharded", "mesh_cluster"]
    sharded, cluster = lines[:-1]
    assert sharded["mesh"] == {"data": 4} and sharded["all_reduce"]
    assert sharded["param_max_abs_diff"] <= sharded["tolerance"]["param_atol"]
    assert cluster["mesh"] == {"data": 4}
