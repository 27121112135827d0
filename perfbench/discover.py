"""Files found by name. Whatever belongs to one cell, configuration,
traffic mix, per-layer metric, reducer, mode, family or torso is a file
`<data_dir>/<kind>/<name>.{json,py}`; a later PR adds one and edits none.
No JAX here: the parent process of a run imports this module.
"""

from __future__ import annotations

import importlib.util
import json
import os


def data(data_dir: str, kind: str, name: str) -> dict:
    with open(os.path.join(data_dir, kind, f"{name}.json")) as f:
        return json.load(f)


def module(data_dir: str, kind: str, name: str):
    """`<data_dir>/<kind>/<name>.py`, loaded afresh; FileNotFoundError
    names the file a later PR has to bring."""
    path = os.path.join(data_dir, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(data_dir: str, section_name: str, section: dict):
    """(algorithm, its `families/<algorithm>.py`): the section's
    `algorithm` key, else the prefix of its name, as the program's
    `load_config` reads it."""
    algorithm = section.get("algorithm", section_name.split("_")[0])
    return algorithm, module(data_dir, "families", algorithm)
