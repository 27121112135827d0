"""Config system: reference-schema parity, new-knob plumbing, validation.

The reference's `config.json` sections must load unchanged
(`utils/config.py` mirrors `utils.check_properties` validation,
`/root/reference/utils.py:33-44` semantics), and every extension knob
added this round (attention/pipeline/MoE/mesh-axis sizes) must flow
from a JSON section into the typed configs.
"""

import json

import pytest

from distributed_reinforcement_learning_tpu.utils.config import (
    RuntimeConfig,
    check_config,
    load_config,
)


def _write(tmp_path, section_name, d):
    p = tmp_path / "config.json"
    p.write_text(json.dumps({section_name: d}))
    return str(p)


class TestReferenceSchema:
    @pytest.mark.parametrize("section,algo", [
        ("impala", "impala"), ("apex", "apex"), ("r2d2", "r2d2"),
        ("impala_cartpole", "impala"), ("xformer", "xformer"),
        ("impala_invaders", "impala"), ("r2d2_pixel", "r2d2"),
        ("r2d2_atari", "r2d2"), ("ximpala", "ximpala"),
        ("ouro_looplm", "looplm"),
    ])
    def test_repo_config_sections_load(self, section, algo):
        agent_cfg, rt = load_config("config.json", section)
        assert rt.algorithm == algo
        assert agent_cfg.num_actions >= 2
        assert rt.num_actors == len(rt.envs) == len(rt.available_action)

    def test_looplm_section_carries_the_published_widths(self):
        import jax.numpy as jnp

        cfg, rt = load_config("config.json", "ouro_looplm")
        assert (cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim,
                cfg.intermediate_size, cfg.vocab_size) == (2048, 16, 128, 5632, 49152)
        assert (cfg.total_ut_steps, cfg.early_exit_threshold, cfg.rope_theta,
                cfg.rms_norm_eps) == (4, 1, 1_000_000, 1e-6)
        assert cfg.num_hidden_layers == 8 and cfg.trajectory == 128
        assert cfg.dtype == jnp.bfloat16 and cfg.start_learning_rate == 1e-5
        assert rt.num_actors * rt.envs_per_actor == 32
        assert rt.envs == ("TokenRecall-v0",)

    @pytest.mark.parametrize("section,default", [("ximpala", "float32"),
                                                 ("ouro_looplm", "bfloat16")])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", None])
    def test_dtype_key_reaches_the_agent_config(self, tmp_path, section,
                                                default, dtype):
        import jax.numpy as jnp

        with open("config.json") as f:
            d = dict(json.load(f)[section])
        d.pop("dtype", None)
        if dtype is not None:
            d["dtype"] = dtype
        cfg, _ = load_config(_write(tmp_path, section, d), section)
        assert cfg.dtype == jnp.dtype(dtype or default).type

    @pytest.mark.parametrize("section", ["ximpala", "ouro_looplm"])
    def test_unknown_dtype_is_an_error(self, tmp_path, section):
        with open("config.json") as f:
            d = dict(json.load(f)[section], dtype="float16")
        with pytest.raises(ValueError, match="dtype 'float16'"):
            load_config(_write(tmp_path, section, d), section)

    def test_looplm_section_without_a_width_is_an_error(self, tmp_path):
        with open("config.json") as f:
            d = dict(json.load(f)["ouro_looplm"])
        del d["intermediate_size"]
        with pytest.raises(KeyError, match="intermediate_size"):
            load_config(_write(tmp_path, "ouro_looplm", d), "ouro_looplm")

    def test_vestigial_keys_accepted(self, tmp_path):
        """Unknown/vestigial reference keys (`config.json:66,105`
        `optimization_method`) load-and-ignore rather than erroring."""
        path = _write(tmp_path, "impala", {
            "model_input": [84, 84, 4], "model_output": 4,
            "env": ["BreakoutDeterministic-v4"], "available_action": [4],
            "num_actors": 1,
            "optimization_method": "impala",        # vestigial in the reference
            "some_future_key": {"nested": True},    # arbitrary unknowns too
        })
        cfg, rt = load_config(path, "impala")
        assert cfg.num_actions == 4 and rt.algorithm == "impala"

    def test_reference_config_loads_unmodified(self):
        """The reference's own config.json (all three sections) loads
        verbatim through this config system (`/root/reference/config.json`)."""
        ref = "/root/reference/config.json"
        import os
        if not os.path.exists(ref):
            pytest.skip("reference tree not present on this host")
        for section, algo in (("impala", "impala"), ("apex", "apex"),
                              ("r2d2", "r2d2")):
            agent_cfg, rt = load_config(ref, section)
            assert rt.algorithm == algo
            assert agent_cfg.num_actions >= 2


class TestExtensionKnobs:
    def test_xformer_parallelism_knobs_flow(self, tmp_path):
        path = _write(tmp_path, "xformer_test", {
            "algorithm": "xformer",
            "model_input": [2], "model_output": 2,
            "env": ["CartPole-v0"], "available_action": [2], "num_actors": 1,
            "seq_len": 16, "burn_in": 4, "d_model": 64, "num_heads": 2,
            "num_layers": 4,
            "attention": "ring_zigzag", "seq_parallel": 2,
            "num_experts": 8, "moe_top_k": 1, "moe_capacity_factor": 1.5,
            "moe_aux_weight": 0.05, "expert_parallel": 2,
            "pipeline_microbatches": 4, "pipeline_stages": 2,
        })
        cfg, rt = load_config(path, "xformer_test")
        assert cfg.attention == "ring_zigzag" and rt.seq_parallel == 2
        assert cfg.num_experts == 8 and cfg.moe_top_k == 1
        assert cfg.moe_capacity_factor == 1.5 and cfg.moe_aux_weight == 0.05
        assert rt.expert_parallel == 2
        assert cfg.pipeline_stages == 2 and cfg.pipeline_microbatches == 4
        assert cfg.pipeline is False  # not set -> off

    def test_pipeline_flag_flows(self, tmp_path):
        path = _write(tmp_path, "xformer_pp", {
            "algorithm": "xformer",
            "model_input": [2], "model_output": 2,
            "env": ["CartPole-v0"], "available_action": [2], "num_actors": 1,
            "num_layers": 2, "pipeline": True,
        })
        cfg, _ = load_config(path, "xformer_pp")
        assert cfg.pipeline is True


class TestValidationParity:
    """`check_config` mirrors the reference's `check_properties` asserts."""

    def test_action_exceeds_model_output(self):
        rt = RuntimeConfig(algorithm="impala", num_actors=1,
                           envs=("PongDeterministic-v4",), available_action=(6,))
        with pytest.raises(ValueError, match="available_action"):
            check_config(rt, num_actions=4)

    def test_actor_env_length_mismatch(self):
        rt = RuntimeConfig(algorithm="impala", num_actors=2,
                           envs=("CartPole-v0",), available_action=(2, 2))
        with pytest.raises(ValueError, match="env"):
            check_config(rt, num_actions=2)

    def test_actor_action_length_mismatch(self):
        rt = RuntimeConfig(algorithm="impala", num_actors=2,
                           envs=("CartPole-v0", "CartPole-v0"),
                           available_action=(2,))
        with pytest.raises(ValueError, match="available_action"):
            check_config(rt, num_actions=2)
