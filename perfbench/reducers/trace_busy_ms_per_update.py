"""Device-busy milliseconds per update: the union of the op line's
intervals in the traced interval over the updates completed in it."""


def reduce(facts: dict, spec: dict):
    trace, n = facts.get("trace"), facts.get("trace_updates")
    if not trace or not n:
        return None
    return 1e3 * trace["busy_s"] / n
