"""Device milliseconds per update under the named scopes: the self time
(`scope_read.scope_seconds`) of the ops under `source_detail.scopes`, each
scope with its children, less those under `source_detail.less`, over the
updates of the traced interval. None where the trace holds no scoped op."""

import scope_read


def reduce(facts: dict, spec: dict):
    n = facts.get("trace_updates")
    src = spec["source_detail"]
    seconds = scope_read.scope_seconds(facts, src["scopes"], src.get("less", ()))
    if not n or seconds is None:
        return None
    return 1e3 * seconds / n
