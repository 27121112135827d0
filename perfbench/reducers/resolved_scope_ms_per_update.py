"""Device milliseconds per update under the named scopes by the program's
own attribution (`attribution_read.ledger`): the ops whose own `op_name`
holds a name under `source_detail.scopes` AND the ops the compiler made
afterwards (async copies, nameless fusions, layout passes) that the
program resolved to such a name from the optimized HLO, less those
under `source_detail.less`. With `source_detail.rules` only what those
rules placed (`serves`: the copies and waits alone). None without a
profile."""

import attribution_read
from scope_read import _under


def reduce(facts: dict, spec: dict):
    n, led = facts.get("trace_updates"), attribution_read.ledger(facts)
    if not n or led is None:
        return None
    src = spec["source_detail"]
    tables = ([led["by_rule"][rule] for rule in src["rules"]]
              if "rules" in src else [led["scopes"]])
    seconds = sum(s for table in tables for scope, s in table.items()
                  if _under(scope, src["scopes"])
                  and not _under(scope, list(src.get("less", ()))))
    return 1e3 * seconds / n
