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


# -- a token family is declared once (ISSUE 45) --------------------------------

import dataclasses  # noqa: E402

from distributed_reinforcement_learning_tpu.agents.token_families import (  # noqa: E402
    TOKEN_FAMILIES)

TOKEN_SECTIONS = {"looplm": "ouro_looplm", "hybridlm": "granite_hybrid",
                  "moelm": "qwen3_next", "mlalm": "joyai_flash",
                  "convlm": "lfm2_moe", "swalm": "smallthinker_moe",
                  "ssmoelm": "nemotron_h_moe"}
RENAMED = {"init_std": "initializer_range"}  # field -> the section's key


def _section(family, **changes):
    with open("config.json") as f:
        section = json.load(f)[TOKEN_SECTIONS[family]]
    return {**section, **changes}


def _load(tmp_path, section):
    return load_config(_write(tmp_path, "s", section), "s")[0]


def _fields(cls, section_key: bool):
    return [f for f in dataclasses.fields(cls)
            if f.metadata.get("section_key", True) is section_key]


def _cases(per_family):
    return [pytest.param(family, item, id=f"{family}-{item}")
            for family, (cls, _) in TOKEN_FAMILIES.items()
            for item in per_family(cls)]


def test_the_table_names_the_committed_sections():
    assert list(TOKEN_FAMILIES) == list(TOKEN_SECTIONS)
    for family, section in TOKEN_SECTIONS.items():
        cfg, rt = load_config("config.json", section)
        assert type(cfg) is TOKEN_FAMILIES[family][0] and rt.algorithm == family


@pytest.mark.parametrize("family", list(TOKEN_FAMILIES))
def test_a_committed_section_is_its_config_built_by_keyword(family):
    """Every field a section may set is the section's value (three
    renames: `initializer_range`, `dtype` by name, `learning_frame` an
    int); every other field is the dataclass's default."""
    import jax.numpy as jnp

    cls = TOKEN_FAMILIES[family][0]
    section = _section(family)
    values = {}
    for f in _fields(cls, True):
        key = RENAMED.get(f.name, f.name)
        if key in section:
            value = section[key]
            values[f.name] = tuple(value) if isinstance(value, list) else value
    values["dtype"] = jnp.dtype(section["dtype"]).type
    values["learning_frame"] = int(section["learning_frame"])
    cfg, _ = load_config("config.json", TOKEN_SECTIONS[family])
    assert cfg == cls(**values) and hash(cfg) == hash(cls(**values))
    assert set(cls.MUST) <= set(section)


@pytest.mark.parametrize("family", list(TOKEN_FAMILIES))
def test_a_section_of_only_its_must_keys_gives_the_defaults(family, tmp_path):
    cls = TOKEN_FAMILIES[family][0]
    full = _section(family)
    section = {k: full[k] for k in (*cls.MUST, "algorithm", "env", "available_action")}
    cfg = _load(tmp_path, section)
    for f in dataclasses.fields(cls):
        if f.name not in cls.MUST:
            assert getattr(cfg, f.name) == f.default, f.name


@pytest.mark.parametrize("family,key", _cases(lambda cls: cls.MUST))
def test_a_missing_must_key_is_a_key_error_naming_it(family, key, tmp_path):
    section = _section(family)
    del section[key]
    with pytest.raises(KeyError, match=key):
        _load(tmp_path, section)


@pytest.mark.parametrize("family,key", _cases(lambda cls: cls.ONLY))
def test_a_refused_value_is_a_value_error_naming_its_key(family, key, tmp_path):
    with pytest.raises(ValueError, match=key):
        _load(tmp_path, _section(family, **{key: "something else"}))


@pytest.mark.parametrize("family,key", _cases(
    lambda cls: [f.name for f in _fields(cls, False)]))
def test_a_field_that_is_no_section_key_does_not_become_one(family, key, tmp_path):
    """`head_block`, `row_block`, `gdn_chunk`, `attention_backend` (and the
    one-pass stubs of the three families that do not loop): a section
    that carries the name is read as if it did not."""
    cls = TOKEN_FAMILIES[family][0]
    default = {f.name: f.default for f in _fields(cls, False)}[key]
    cfg = _load(tmp_path, _section(family, **{key: "not read"}))
    assert getattr(cfg, key) == default


def test_the_shared_fields_are_written_once():
    """What the four families share is `TokenLMConfig`'s; a family's class
    redefines of it only a default of its own."""
    from distributed_reinforcement_learning_tpu.agents.looplm import TokenLMConfig

    shared = {f.name: f.default for f in dataclasses.fields(TokenLMConfig)}
    own = {"looplm": {"trajectory", "total_ut_steps", "exit_entropy_coef"},
           "hybridlm": {"rms_norm_eps"}, "moelm": set(), "mlalm": {"trajectory"},
           "convlm": set(), "swalm": {"trajectory"}, "ssmoelm": {"trajectory"}}
    for family, (cls, agent) in TOKEN_FAMILIES.items():
        assert issubclass(cls, TokenLMConfig)
        redefined = {name for name in shared if name in cls.__dict__.get(
            "__annotations__", {})}
        assert redefined == own[family], family
        assert agent.__init__.__annotations__["cfg"] == cls.__name__


@pytest.mark.parametrize("path", [
    "distributed_reinforcement_learning_tpu/utils/config.py",
    "distributed_reinforcement_learning_tpu/runtime/launch.py",
    "train_ximpala.py"])
def test_the_family_names_are_spelled_in_one_place(path):
    """No quoted `looplm` / `hybridlm` / `moelm` / `mlalm` in the
    configuration reader or the launchers: they read
    `agents/token_families.TOKEN_FAMILIES` (after `tests/test_scopes.py`'s
    test of the scope names)."""
    import re

    with open(path) as f:
        text = f.read()
    quoted = re.compile("[\"'](%s)[\"']" % "|".join(TOKEN_FAMILIES))
    assert quoted.findall(text) == []
    if path.endswith("config.py"):
        assert not re.search(r"^\s*(from|import) \S*\.models\b", text, re.M)
        assert text.count("TOKEN_FAMILIES[") == 1
