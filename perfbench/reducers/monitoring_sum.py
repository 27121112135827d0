"""Seconds of the `jax.monitoring` duration events named in
`source_detail.events`, summed over the process's set-up (before the window)."""


def reduce(facts: dict, spec: dict):
    seconds = facts.get("setup_monitoring", {}).get("seconds")
    if not seconds:
        return None
    return sum(seconds.get(e, 0.0) for e in spec["source_detail"]["events"])
