"""`run.py` end to end on the CPU at a CartPole size, once per mode,
through the script's test-only `--expect-platform`; and the refusal to
print a result where JAX finds no accelerator."""

import json
import os
import subprocess
import sys

import pytest

import contract
from conftest import BENCH_DIR, ROOT


def _run(data_copy, cell, trace, seconds="3", platform="cpu", seed="3000000019"):
    cmd = [sys.executable, os.path.join(BENCH_DIR, "run.py"),
           "--workload", cell, "--seed", seed, "--seconds", seconds,
           "--trace", str(trace), "--data-dir", data_copy["dir"],
           "--benchmark", data_copy["benchmark"]]
    if platform:
        cmd += ["--expect-platform", platform]
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)


@pytest.mark.parametrize("cell, trace", [("tiny_impala.hostloop", 1),
                                         ("tiny_impala.anakin", 0)])
def test_cpu_rehearsal_prints_a_contract_line(data_copy, cell, trace):
    proc = _run(data_copy, cell, trace)
    assert proc.returncode == 0, (proc.stderr[-2000:], proc.stdout[-2000:])
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    contract.check_line(line, data_copy["bench"], cell, bool(trace), chips=1)
    assert line["correct"] is True, proc.stdout[-3000:]
    assert line["device"]["platform"] == "cpu"  # never published
    assert line["attempted"] > 0 and line["failed"] == 0
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert line["breakdown"]["device_ops"]
        assert "batch_wait_ms" in line["metrics"]
    else:
        assert line["metrics"]["frames_learned_per_s"]["value"] > 0


def test_no_accelerator_means_no_result_line(data_copy):
    proc = _run(data_copy, "tiny_impala.anakin", 0, platform=None)
    assert proc.returncode != 0
    assert "{" not in proc.stdout, proc.stdout
    assert "NO DEVICE" in proc.stderr


def test_unknown_workload_means_no_result_line(data_copy):
    proc = _run(data_copy, "no_such.cell", 0)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
