"""The program's own ledger of a traced run
(`distributed_reinforcement_learning_tpu/observability/attribution.py`):
every HLO op's self time under the scope it serves, resolved from the
optimized HLO that the run's own `.xplane.pb` carries. Made once per run
and kept in `facts`. No JAX here (the module is loaded by its path; it
imports none): the reducers run in `run.py`'s parent process.

A program from before PR 34 has no resolver. Its ledger is the `own`
view alone, every op under the deepest name in its own `op_name` and
nothing placed by `inside` or `serves`: what that program can say of
itself, and a true reading (the resolved metrics then equal the ones
that read own names, the staging reads 0, the unresolved share equals
the unscoped one). `contract.check_line` fails a traced run whose line
lacks a listed metric, so None is kept for a run without a profile.

A test hands a recording: `facts["scope_recording"]` with, beside
`scope_read`'s keys, `"hlo_rows": [[program_id, hlo_op_name, op_name,
self_us], ...]` and `"hlo_text": {program_id: HLO text}`; without them
the `hlo_stats` rows are read with no HLO behind them.
"""

from __future__ import annotations

import importlib.util
import os

import scope_read

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESOLVER = os.path.join(ROOT, "distributed_reinforcement_learning_tpu",
                        "observability", "attribution.py")


def resolver(path: str = RESOLVER):
    """The program's `attribution` module, None where it has none."""
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("drl_attribution", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _own_view(rows: list, names: list[str]) -> dict:
    """The ledger a program without a resolver has: own names only."""
    own: dict[str, float] = {}
    unresolved: dict[str, float] = {}
    for hlo, op_path, self_us in rows:
        scope = scope_read.scope_of(op_path, names)
        into, key = (unresolved, hlo) if scope is None else (own, scope)
        into[key] = into.get(key, 0.0) + self_us / 1e6
    return {"total_s": sum(r[2] for r in rows) / 1e6, "scopes": own,
            "own": own, "by_rule": {"own": own, "inside": {}, "serves": {}},
            "holds_other_scopes": {},
            "unresolved": [[n, s, "the program has no resolver"] for n, s in
                           sorted(unresolved.items(), key=lambda kv: -kv[1])]}


def ledger(facts: dict) -> dict | None:
    cache = facts.setdefault("_scope_read", {})
    if "ledger" not in cache:
        cache["ledger"] = _make(facts)
    return cache["ledger"]


def _make(facts: dict) -> dict | None:
    names = scope_read.vocabulary(facts["data_dir"])
    attribution = resolver(facts.get("resolver_path", RESOLVER))
    recording = facts.get("scope_recording")
    if attribution is None or (recording and "hlo_rows" not in recording):
        rows = scope_read.hlo_stats(facts)
        if not rows:
            return None
        if attribution is None:
            return _own_view(rows, names)
        return attribution.account([["", *row] for row in rows], {}, names)
    if recording:
        return attribution.account(
            recording["hlo_rows"],
            {pid: attribution.parse_hlo(text)
             for pid, text in recording.get("hlo_text", {}).items()}, names)
    return attribution.ledger(
        os.path.join(facts.get("run_dir", ""), "profile"), names)
