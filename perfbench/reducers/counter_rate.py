"""Per-second rate of telemetry counter `source_detail.counter`, summed over
the shards of role `source_detail.role`, between its first and last flush
inside the measured window."""

import telemetry_read


def reduce(facts: dict, spec: dict):
    src = spec["source_detail"]
    rates = [telemetry_read.counter_rate(records, src["counter"],
                                         facts["t0"], facts["t1"])
             for records in telemetry_read.shards(
                 facts.get("telemetry_dir", ""), src["role"]).values()]
    rates = [r for r in rates if r is not None]
    return sum(rates) if rates else None
