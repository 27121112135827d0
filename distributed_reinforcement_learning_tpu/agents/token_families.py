"""The token families: a section's `algorithm` -> (the class of its
configuration, the class of its agent). The ONE place that names them:
`utils.config.load_config` reads a section through the first,
`runtime/launch.train_anakin_tokens` builds the second. Adding a family
is its model file, its agent file with its config class
(`agents/looplm.TokenLMConfig`), one row here and a section of
`config.json`. Imported only where a token section is at hand: an Atari
section loads no language model.
"""

from distributed_reinforcement_learning_tpu.agents.convlm import (
    ConvLMAgent, ConvLMConfig)
from distributed_reinforcement_learning_tpu.agents.hybridlm import (
    HybridLMAgent, HybridLMConfig)
from distributed_reinforcement_learning_tpu.agents.looplm import (
    LoopLMAgent, LoopLMConfig)
from distributed_reinforcement_learning_tpu.agents.mlalm import (
    MLALMAgent, MLALMConfig)
from distributed_reinforcement_learning_tpu.agents.moelm import (
    MoELMAgent, MoELMConfig)
from distributed_reinforcement_learning_tpu.agents.ssmoelm import (
    SSMoELMAgent, SSMoELMConfig)
from distributed_reinforcement_learning_tpu.agents.swalm import (
    SwaLMAgent, SwaLMConfig)

TOKEN_FAMILIES = {
    "looplm": (LoopLMConfig, LoopLMAgent),
    "hybridlm": (HybridLMConfig, HybridLMAgent),
    "moelm": (MoELMConfig, MoELMAgent),
    "mlalm": (MLALMConfig, MLALMAgent),
    "convlm": (ConvLMConfig, ConvLMAgent),
    "swalm": (SwaLMConfig, SwaLMAgent),
    "ssmoelm": (SSMoELMConfig, SSMoELMAgent),
}
