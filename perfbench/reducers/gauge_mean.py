"""Mean over the shards of role `source_detail.role` of the window mean of
telemetry gauge `source_detail.gauge` (flushes inside the measured window)."""

import telemetry_read


def reduce(facts: dict, spec: dict):
    src = spec["source_detail"]
    per_shard = []
    for records in telemetry_read.shards(
            facts.get("telemetry_dir", ""), src["role"]).values():
        mean = telemetry_read.gauge_window_mean(
            records, src["gauge"], facts["t0"], facts["t1"])
        if mean is not None:
            per_shard.append(mean)
    return sum(per_shard) / len(per_shard) if per_shard else None
