"""100 * (1 - busy_s / window_s) of the traced interval."""


def reduce(facts: dict, spec: dict):
    trace = facts.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
