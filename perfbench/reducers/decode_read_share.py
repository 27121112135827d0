"""The decode steps' share of their memory roofline where a step reads
its WEIGHTS AND ITS CACHES: the bytes the traced decode steps must read,
counted HERE from the configuration's shapes, over the published HBM
bytes a second, held against the device seconds that the program's own
attribution (`attribution_read.ledger`) places under
`source_detail.scopes` (`collect/act` and below, by every rule: what
`*_decode_resolved_ms_per_update` reads).

Counted, a step: every layer's attention matrices (q, k, v, o) and the
untied vocabulary head WHOLE, in bfloat16; the routers in float32; of a
layer's HELD experts those that some row chose (the run's own counter
`held_experts_touched_mean` x one expert's gate, up and down in bfloat16:
the grouped product of the sorted form reads the touched experts alone);
the global layers' key/value caches as far as the step's scan reads them
(the chunk's own `decode_spans`: a static prefix) and the window layers'
rings as far as `min(span, W)`, keys and values in bfloat16 for every
row. NOT counted: the embedding's gathered rows, the norms, the record of
the experts chosen, what a step WRITES, any activation. So it is a lower
bound on what travels, and the share can only read low. None without a
profile, the counter, the spans, or for a section this count does not
know."""

import attribution_read
import peaks
from scope_read import _under


def step_bytes(section: dict, rows: int, spans, touched: float) -> dict:
    """{part: mean bytes one decode step reads} over an episode of
    `trajectory` steps at `rows` rows, under the scans' `spans` (each the
    exclusive end of its steps and the prefix they read) with `touched`
    held experts a layer a step."""
    d, t = section["hidden_size"], section["trajectory"]
    heads, kv, hd = (section["num_attention_heads"],
                     section["num_key_value_heads"], section["head_dim"])
    layout = list(section["sliding_window_layout"])
    layers, ring = len(layout), min(section["sliding_window_size"], t)
    steps = [hi - lo for lo, hi in zip((0, *spans), spans)]
    position = 2 * rows * kv * hd * 2  # keys and values of one position, bfloat16
    return {
        "attention": 2 * layers * (2 * d * heads * hd + 2 * d * kv * hd),
        "head": 2 * section["vocab_size"] * d,
        "routers": 4 * layers * d * section["router_width"],
        "experts": 2 * layers * touched * 3 * d * section["moe_ffn_hidden_size"],
        "global_cache": layout.count(0) * position
        * sum(n * span for n, span in zip(steps, spans)) / t,
        "rings": layout.count(1) * position
        * sum(n * min(span, ring) for n, span in zip(steps, spans)) / t}


def reduce(facts: dict, spec: dict):
    n, led = facts.get("trace_updates"), attribution_read.ledger(facts)
    section = facts.get("section", {})
    spans = facts.get("static", {}).get("decode_spans")
    touched = facts.get("counters", {}).get("held_experts_touched_mean")
    if (not n or led is None or not spans or touched is None
            or "sliding_window_layout" not in section):
        return None
    seconds = sum(s for scope, s in led["scopes"].items()
                  if _under(scope, spec["source_detail"]["scopes"]))
    if not seconds:
        return None
    peak = peaks.device_peaks(facts["device"]["kind"])["hbm_bytes_per_s"]
    rows = facts.get("num_envs") or (section["envs_per_actor"]
                                     * section["num_actors"])
    parts = step_bytes(section, rows, spans, touched)
    size, steps = sum(parts.values()), n * section["trajectory"]
    facts.setdefault("notes", []).append(
        f"decode: {steps} steps read at least {size / 1e6:.1f} MB each "
        f"({', '.join(f'{k} {v / 1e6:.1f}' for k, v in parts.items())}), "
        f"{1e3 * size / peak:.3f} ms a step at HBM's peak; "
        f"{1e3 * seconds / steps:.3f} ms a step under "
        f"{spec['source_detail']['scopes']}")
    return 100.0 * size * steps / peak / seconds
