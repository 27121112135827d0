"""The V-trace Pallas kernel's share of its roofline: the least time the
chip could take for one call (`flops.vtrace_kernel_cost` on the `[T-2,
B]` views the learn step passes, against the published peaks) over the
mean device time of the trace's events that match `source_detail.pattern`.
Which bound applies is printed on an earlier line.

NOT listed in BENCHMARK.json (PERF.md, Open questions): the recorded
trace shows the kernel's operands laid out in the fast memory space
(`S(1)` in the op's detail), so they do not cross HBM and the HBM-bound
least time is not the least time; at `[18, 2048]` the share read 95 %
with a consumer fusion averaged in. The PR that lists it has to count
the bytes that really travel."""

import flops
import peaks
import trace_reduce


def reduce(facts: dict, spec: dict):
    trace = facts.get("trace")
    if not trace:
        return None
    seconds, calls = trace_reduce.match_totals(
        trace["op_totals"], trace["details"], spec["source_detail"]["pattern"])
    if not calls:
        return None
    section = facts["section"]
    batch = facts.get("learn_batch") or section["batch_size"]
    cost = flops.vtrace_kernel_cost(section.get("trajectory", 20) - 2, batch)
    least, bound = flops.roofline_seconds(
        cost, peaks.device_peaks(facts["device"]["kind"]))
    facts.setdefault("notes", []).append(
        f"vtrace kernel: {calls} calls, mean {1e6 * seconds / calls:.3f} us, "
        f"roofline {1e9 * least:.1f} ns ({bound} bound)")
    return 100.0 * least / (seconds / calls)
