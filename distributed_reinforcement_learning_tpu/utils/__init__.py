"""Config, logging, checkpointing, profiling utilities (reference layer L5).

Import the submodule you need (`utils.config`, `utils.logger`, ...): this
package imports none of them itself, so the lowest layers can read a knob
through `utils.environ` without loading the agents or a metrics writer.
"""
