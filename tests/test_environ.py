"""The one reader of `DRL_*` environment knobs (utils/environ.py), the
knobs whose hand-written parsers used to read a looser grammar, and the
launcher that asks the package for its defaults."""

import importlib.util
import platform
import signal
import sys
from pathlib import Path

import pytest

from distributed_reinforcement_learning_tpu.data import device_path
from distributed_reinforcement_learning_tpu.runtime import (
    actor_pipeline,
    learner_tier,
    replay_shard,
    weight_shards,
)
from distributed_reinforcement_learning_tpu.utils.environ import (
    env_flag,
    env_float,
    env_int,
)

REPO = Path(__file__).resolve().parent.parent
KNOB = "ENVIRON_TEST_KNOB"  # the reader does not care about the prefix

ON = ("1", "true", "yes", "on")
OFF = ("0", "false", "no", "off")


@pytest.mark.parametrize("token", [t for w in ON for t in (w, w.upper())])
def test_flag_on_tokens(monkeypatch, token):
    monkeypatch.setenv(KNOB, token)
    assert env_flag(KNOB, False) is True


@pytest.mark.parametrize("token", [t for w in OFF for t in (w, w.upper())])
def test_flag_off_tokens(monkeypatch, token):
    monkeypatch.setenv(KNOB, token)
    assert env_flag(KNOB, True) is False


@pytest.mark.parametrize("value", [None, "", "  "])
@pytest.mark.parametrize("default", [True, False])
def test_flag_unset_or_empty_is_the_default(monkeypatch, value, default):
    if value is None:
        monkeypatch.delenv(KNOB, raising=False)
    else:
        monkeypatch.setenv(KNOB, value)
    assert env_flag(KNOB, default) is default


@pytest.mark.parametrize("token", ["2", "enable", "of", "-1"])
def test_flag_unknown_token_names_the_knob_and_the_grammar(monkeypatch, token):
    monkeypatch.setenv(KNOB, token)
    with pytest.raises(ValueError) as e:
        env_flag(KNOB, False)
    msg = str(e.value)
    assert KNOB in msg and repr(token) in msg
    assert "1|true|yes|on" in msg and "0|false|no|off" in msg


def test_int_reader(monkeypatch):
    monkeypatch.delenv(KNOB, raising=False)
    assert env_int(KNOB, 7) == 7
    monkeypatch.setenv(KNOB, "")
    assert env_int(KNOB, 7) == 7
    monkeypatch.setenv(KNOB, " -3 ")
    assert env_int(KNOB, 7) == -3
    monkeypatch.setenv(KNOB, "2.5")
    with pytest.raises(ValueError, match=f"{KNOB} must be an integer"):
        env_int(KNOB, 7)


def test_float_reader(monkeypatch):
    monkeypatch.delenv(KNOB, raising=False)
    assert env_float(KNOB, 0.5) == 0.5
    monkeypatch.setenv(KNOB, "")
    assert env_float(KNOB, 0.5) == 0.5
    monkeypatch.setenv(KNOB, "2")
    assert env_float(KNOB, 0.5) == 2.0
    monkeypatch.setenv(KNOB, "fast")
    with pytest.raises(ValueError, match=f"{KNOB} must be a number"):
        env_float(KNOB, 0.5)


# The two gates that were read as `env != "0"`: every spelling of "off"
# but `0` used to switch the path ON.
@pytest.mark.parametrize("knob,resolve", [
    ("DRL_REPLAY_SPILL", replay_shard.spill_auto_enabled),
    ("DRL_DEVICE_PATH", device_path.device_path_enabled),
])
@pytest.mark.parametrize("token", ["off", "false", "no"])
def test_formerly_lenient_gates_read_off_as_off(monkeypatch, knob, resolve,
                                                token):
    monkeypatch.setenv(knob, token)
    assert resolve() is False


def test_actor_pipe_reads_the_whole_grammar(monkeypatch):
    # Was `== "1"` / `== "0"` only: `true` fell through to the default.
    monkeypatch.setenv("DRL_ACTOR_PIPE", "true")
    assert actor_pipeline.pipeline_enabled() is True


@pytest.mark.parametrize("knob,resolve", [
    ("DRL_REPLAY_SPILL", replay_shard.spill_auto_enabled),
    ("DRL_DEVICE_PATH", device_path.device_path_enabled),
    ("DRL_ACTOR_PIPE", actor_pipeline.pipeline_enabled),
    ("DRL_WEIGHTS_QUANT", weight_shards.quant_mode),
    ("DRL_COLL_QUANT", learner_tier.coll_quant),
])
def test_gate_rejects_an_unknown_token(monkeypatch, knob, resolve):
    monkeypatch.setenv(knob, "maybe")
    with pytest.raises(ValueError, match=knob):
        resolve()


@pytest.mark.parametrize("knob,resolve,mode", [
    ("DRL_WEIGHTS_QUANT", weight_shards.quant_mode, "int8"),
    ("DRL_COLL_QUANT", learner_tier.coll_quant, "bf16"),
    ("DRL_COLL_QUANT", learner_tier.coll_quant, "f32"),
])
def test_quant_knobs_also_name_a_mode(monkeypatch, knob, resolve, mode):
    monkeypatch.setenv(knob, mode.upper())
    assert resolve() == mode


# -- the launcher asks the package ---------------------------------------------

GATE_KNOBS = (
    "DRL_SHM_RING", "DRL_SHM_WEIGHTS", "DRL_CODEC_CACHE", "DRL_OBS_DEDUP",
    "DRL_WEIGHTS_SHARDED", "DRL_WEIGHTS_QUANT", "DRL_WEIGHTS_DELTA",
    "DRL_REPLAY_SHARDS", "DRL_REPLAY_SPILL", "DRL_ACTOR_PRIORITY",
    "DRL_ADMISSION", "DRL_DEVICE_PATH", "DRL_LEARNER_SEATS",
    "DRL_LEARNER_SYNC", "DRL_COLL_QUANT", "DRL_COLL_OVERLAP",
    "DRL_INFER_REPLICAS", "DRL_ACTOR_PIPE",
)


class _ExitedChild:
    """What `subprocess.Popen` hands the launcher in these tests: a
    child that wrote nothing and has already exited 0."""

    pid = 0
    stdout = ()

    def poll(self):
        return 0

    def wait(self, timeout=None):
        return 0

    def terminate(self):
        pass

    kill = terminate


@pytest.fixture
def plan(monkeypatch):
    """Run the launcher's `main()` with these arguments and get back the
    (command, environment) of every process it would have started."""
    spec = importlib.util.spec_from_file_location(
        "launch_local_cluster", REPO / "scripts" / "launch_local_cluster.py")
    launcher = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(launcher)
    for knob in GATE_KNOBS:
        monkeypatch.delenv(knob, raising=False)
    spawned = []

    def popen(cmd, env=None, **_):
        spawned.append((cmd, env))
        return _ExitedChild()

    monkeypatch.setattr(launcher.subprocess, "Popen", popen)
    monkeypatch.setattr(signal, "signal", lambda *_: None)

    def run(*argv):
        monkeypatch.setattr(sys, "argv", ["launch_local_cluster.py", *argv])
        with pytest.raises(SystemExit) as e:
            launcher.main()
        return e.value.code, spawned

    return run


def _role(cmd):
    return cmd[cmd.index("--mode") + 1]


def test_launcher_plans_the_default_topology(plan, monkeypatch):
    rc, spawned = plan("--section", "r2d2", "--actors", "2", "--updates", "1",
                       "--serve_inference", "--remote_act")
    assert rc == 0
    assert [_role(cmd) for cmd, _ in spawned] == ["learner", "actor", "actor"]
    (_, learner_env), *actors = spawned
    # No rings, no seats, no replicas.
    for _, env in spawned:
        assert "DRL_SHM_RING_CREATE" not in env
        assert "DRL_SHM_RING_NAME" not in env
        assert "DRL_LEARNER_SEATS" not in env
        assert "DRL_INFER_ADDRS" not in env
    # The weight board where the seqlock's ordering argument holds.
    board = learner_env.get("DRL_SHM_WEIGHTS_CREATE")
    assert (board is not None) is (
        platform.machine().lower() in ("x86_64", "amd64"))
    assert all(env.get("DRL_SHM_WEIGHTS_NAME") == board for _, env in actors)
    # What the learner it starts then resolves: two replay shards, with
    # the spill tier.
    for knob in GATE_KNOBS:
        if knob in learner_env:
            monkeypatch.setenv(knob, learner_env[knob])
    assert replay_shard.shard_count() == 2
    assert replay_shard.spill_config(spill_dir="unused") is not None


def test_launcher_forced_topology_and_malformed_knob(plan, monkeypatch, capsys):
    monkeypatch.setenv("DRL_SHM_RING", "on")
    monkeypatch.setenv("DRL_SHM_WEIGHTS", "off")
    monkeypatch.setenv("DRL_INFER_REPLICAS", "1")
    rc, spawned = plan("--actors", "1", "--updates", "1",
                       "--serve_inference", "--remote_act")
    assert rc == 0
    assert [_role(cmd) for cmd, _ in spawned] == [
        "learner", "inference", "actor"]
    assert "DRL_SHM_RING_CREATE" in spawned[0][1]
    assert "DRL_SHM_WEIGHTS_CREATE" not in spawned[0][1]
    assert "DRL_INFER_ADDRS" in spawned[2][1]
    monkeypatch.setenv("DRL_INFER_REPLICAS", "two")
    rc, _ = plan("--actors", "1", "--serve_inference", "--remote_act")
    assert rc == 2  # argparse's p.error
    assert "DRL_INFER_REPLICAS must be an integer" in capsys.readouterr().err
