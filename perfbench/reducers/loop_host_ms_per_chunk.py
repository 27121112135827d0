"""Host milliseconds a chunk of the fused loop outside its wait for the
device, from the program's LOG: over `source_detail.spans`, the sum of
each span's mean over the window's chunks (`start_read.window_chunks`:
every chunk of the window, in every cell, whatever the profile's
converter hands back). The harness's own profiler starts and stops
inside a dispatch: those two dispatches are left out of that span's mean.

A program without the record: the same spans on the profile's host plane
where `host_span_ms_per_chunk` finds them, else the idle milliseconds a
traced chunk (`window_s - busy_s` of the traced interval over the chunks
it covers): what the device waited for the host, which is what the spans
would bound. Each fallback says so in a note."""

import os

import discover
import start_read

HERE = os.path.dirname(os.path.abspath(__file__))


def reduce(facts: dict, spec: dict):
    names = spec["source_detail"]["spans"]
    notes = facts.setdefault("notes", [])
    chunks = start_read.window_chunks(facts)
    if chunks:
        total = 0.0
        for name in names:
            seen = [c["spans"].get(name, 0.0) for c in chunks
                    if not (c["profiler"] and name == "anakin/dispatch")]
            if seen:
                total += sum(seen) / len(seen)
            else:
                notes.append(f"loop_host_ms_per_chunk: every {name} of the "
                             f"window holds a call of the harness's "
                             f"profiler: left out")
        return 1e3 * total
    value = discover.module(os.path.dirname(HERE), "reducers",
                            "host_span_ms_per_chunk").reduce(facts, spec)
    if value is not None:
        notes.append("loop_host_ms_per_chunk: no chunk line in the log; read "
                     "from the profile's host plane, as host_ms_per_chunk")
        return value
    trace = facts.get("trace")
    if not trace:
        return None
    chunks_traced = max(1.0, facts.get("trace_updates", 0)
                        / max(1, facts.get("chunk_updates", 1)))
    notes.append("loop_host_ms_per_chunk: no chunk line in the log and no "
                 "span on the profile's host plane; the traced interval's "
                 f"idle time over its {chunks_traced:g} chunks")
    return 1e3 * (trace["window_s"] - trace["busy_s"]) / chunks_traced
