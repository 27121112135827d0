"""Milliseconds per update that the learner's thread spent inside the
stages named in `source_detail.stages`: the summed durations of those spans
(the program's `StageTimer`, one span per invocation in the Chrome
trace `trace-learner-0.json`) that START inside the measured window,
over the updates completed in it. Stages a family does not have add
nothing; nested stages must not be listed with their parent."""

import os

import telemetry_read


def reduce(facts: dict, spec: dict):
    path = os.path.join(facts.get("telemetry_dir", ""), "trace-learner-0.json")
    stages = set(spec["source_detail"]["stages"])
    spans = [s for s in telemetry_read.host_spans(path)
             if s[0] in stages and facts["t0"] <= s[1] <= facts["t1"]]
    if not spans or not facts.get("updates"):
        return None
    return 1e3 * sum(e - s for _, s, e in spans) / facts["updates"]
