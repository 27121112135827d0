"""utils/device.py: where compiled programs are kept, and the line that
says which device a process opened."""

import os
import tempfile
from pathlib import Path

import jax
import pytest

from distributed_reinforcement_learning_tpu.utils import device

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def cache_dir_config():
    """Restore jax's cache-dir setting (conftest keeps the cache itself
    off through JAX_ENABLE_COMPILATION_CACHE=0, whatever the dir)."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outside")
    assert device.enable_compile_cache() == "/somewhere/outside"
    # JAX reads the variable itself; the helper set no directory in code.
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = device.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # The path is part of the cache key: nothing that changes from one
    # process to the next may be in it.
    assert str(os.getpid()) not in path
    assert not path.startswith(tempfile.gettempdir())
    assert ".jax_cache/" in (REPO / ".gitignore").read_text()


def test_two_calls_give_the_same_path(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert device.enable_compile_cache() == device.enable_compile_cache()


def test_tests_keep_the_cache_off():
    assert jax.config.jax_enable_compilation_cache is False


def test_open_devices_prints_where_the_process_runs(capsys):
    info = device.open_devices("learner")
    assert info == {"platform": "cpu", "kind": "cpu",
                    "count": len(jax.devices())}
    assert capsys.readouterr().err.strip() == f"[learner] device: {info}"


def test_open_devices_reports_what_jax_reports(monkeypatch):
    chip = type("D", (), {"platform": "tpu", "device_kind": "TPU v5 lite"})()
    monkeypatch.setattr(jax, "devices", lambda: [chip] * 4)
    assert device.open_devices("learner") == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}
