"""Utilization of the device by the learn step WHILE IT RUNS, not a
kernel's roofline share: operations one update needs (forward +
backward + target network, from shapes: the family's
`learn_flops_per_update` over the torso's count; acting steps of the
fused loop and recomputation are not counted) times the updates of the
traced interval, over the device's busy seconds in it (the union of the
op line) and chips times the published bf16 peak. How long the device
waits for the host is `device_idle_share`, not this. The sections
compute in float32, whose matmuls the MXU runs as bf16 passes: against
the bf16 peak that reads low, and says so."""

import discover
import flops
import peaks


def reduce(facts: dict, spec: dict):
    trace, n = facts.get("trace"), facts.get("trace_updates")
    if not trace or not n:
        return None
    peak = peaks.device_peaks(facts["device"]["kind"])["bf16_flops_per_s"]
    family = discover.module(facts["data_dir"], "families", facts["algorithm"])
    per_update = family.learn_flops_per_update(
        facts["section"], flops.torso_macs(facts["data_dir"], facts["section"]),
        facts.get("learn_batch"))
    return 100.0 * per_update * n / (trace["busy_s"] * facts["chips"] * peak)
