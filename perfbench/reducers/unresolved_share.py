"""Share of the device's busy seconds that the program's own attribution
(`attribution_read.ledger`) places under no scope, by none of its rules:
`while` self time, fusions of nameless instructions, copies whose
consumer chain ends in a parameter. Under `*_unscoped_share` by what
`inside` and `serves` placed; equal to it for a program without a
resolver. None without a profile."""

import attribution_read


def reduce(facts: dict, spec: dict):
    trace, led = facts.get("trace"), attribution_read.ledger(facts)
    if not trace or led is None:
        return None
    return 100.0 * sum(s for _n, s, _w in led["unresolved"]) / trace["busy_s"]
