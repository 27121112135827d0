"""Share of the device's busy seconds under none of `source_detail.scopes`
(the roots of the vocabulary): how whole the split by scope is. What is
left are ops the compiler made after the scopes were written (layout
copies, async slices, packed masks), which carry no `op_name` or only
the outer loop's. 100 where no op is scoped at all — a program without
scopes, or a stale executable from a shared compile cache — which is a
true reading and not a missing one; None without a profile."""

import scope_read


def reduce(facts: dict, spec: dict):
    trace, rows = facts.get("trace"), scope_read.hlo_stats(facts)
    if not trace or not rows:
        return None
    scoped = scope_read.scope_seconds(facts, spec["source_detail"]["scopes"])
    total = sum(self_us for _hlo, _path, self_us in rows) / 1e6
    return 100.0 * (total - (scoped or 0.0)) / trace["busy_s"]
