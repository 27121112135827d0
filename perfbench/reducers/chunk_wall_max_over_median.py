"""The window's longest chunk over its median chunk, by the program's
own spans (`start_read.window_chunks`: a chunk runs from its
`anakin/step_read` to the next chunk's), less the chunks in which the
harness's profiler started and stopped. A note names what held the
longest chunk's excess over its own median: `dispatch` is the host (a
compile, a collector pass), `wait` the device or the runtime, `between`
the loop's glue after the chunk's last span, which no span covers.

A program without the record, or a window whose every chunk holds a call
of the profiler: the same from `facts["chunk_seconds"]` (entry to entry
of `train_chunk`, nothing to name), with a note; 1.0 for a loop that has
no chunks."""

import statistics

import start_read


def reduce(facts: dict, spec: dict):
    notes = facts.setdefault("notes", [])
    chunks = [c for c in start_read.window_chunks(facts) or ()
              if not c["profiler"]]
    if chunks:
        longest = max(chunks, key=lambda c: c["seconds"])
        excess = {name: seconds - statistics.median(
            c["spans"].get(name, 0.0) for c in chunks)
            for name, seconds in longest["spans"].items()}
        held = max(excess, key=excess.get)
        notes.append(
            f"chunk_wall_max_over_median: {len(chunks)} chunks, the longest "
            f"{longest['seconds']:.4f} s at {longest['wall']:.3f}; {held} "
            f"held {excess[held]:.4f} s over its median")
        return longest["seconds"] / statistics.median(
            c["seconds"] for c in chunks)
    seconds, at, kept = facts.get("chunk_seconds") or [], facts.get("t0", 0.0), []
    for s in seconds:
        if not any(at <= t <= at + s for t in start_read.traced_instants(facts)):
            kept.append(s)
        at += s
    if not kept:
        notes.append("chunk_wall_max_over_median: this loop has no chunks")
        return 1.0
    notes.append("chunk_wall_max_over_median: no chunk line in the log "
                 "outside the profiler's own calls; from the observer's "
                 "entry-to-entry seconds")
    return max(kept) / statistics.median(kept)
