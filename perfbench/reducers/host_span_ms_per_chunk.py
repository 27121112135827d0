"""Host milliseconds per chunk of the fused loop outside its wait for
the device: over `source_detail.spans`, the sum of each span's mean
duration in the traced interval (the program's `chip_span`s, on the host
plane of the profile). A span that never occurs (no checkpoint
directory) adds nothing; None where none of them occurs."""

import scope_read


def reduce(facts: dict, spec: dict):
    events = scope_read.host_spans(facts)
    if not events:
        return None
    durations: dict[str, list] = {}
    for name, _start_us, dur_us in events:
        if name in spec["source_detail"]["spans"]:
            durations.setdefault(name, []).append(dur_us)
    if not durations:
        return None
    return sum(sum(d) / len(d) for d in durations.values()) / 1e3
