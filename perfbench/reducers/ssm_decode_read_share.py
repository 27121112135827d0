"""The decode steps' share of their memory roofline where a step reads
its weights AND reads and writes a RECURRENT STATE: the bytes the traced
decode steps must move, counted HERE from the configuration's shapes
(`hybrid_override_pattern`: one character a layer), over the published
HBM bytes a second, held against the device seconds that the program's
own attribution (`attribution_read.ledger`) places under
`source_detail.scopes` (`collect/act` and below, by every rule: what
`*_decode_resolved_ms_per_update` reads). `reducers/decode_read_share.py`
is the same arithmetic for a stack of attention layers on caches and
rings; this one knows the three kinds of layer of `families/ssmoelm.py`.

Counted, a step: every `M` layer's in- and out-projection, every `*`
layer's q, k, v and o, every `E` layer's shared expert and the untied
vocabulary head WHOLE, in bfloat16; the routers in float32; of a layer's
HELD experts those that some row chose (the run's own counter
`held_experts_touched_mean` x one expert's up and down matrices in
bfloat16: the grouped product of the sorted form reads the touched
experts alone); every `M` layer's recurrent state READ AND WRITTEN (float32,
every row: twice its bytes) and its window likewise; the `*` layers'
key/value cache as far as the step's scan reads it (the chunk's own
`decode_spans`: a static prefix), keys and values in bfloat16 for every
row. NOT counted: the embedding's gathered rows, the norms, the taps,
the record of the experts chosen, the cache's write, any activation. So
it is a lower bound on what travels, and the share can only read low.
None without a profile, the counter, the spans, or for a section this
count does not know."""

import attribution_read
import peaks
from scope_read import _under


def step_bytes(section: dict, rows: int, spans, touched: float) -> dict:
    """{part: mean bytes one decode step moves} over an episode of
    `trajectory` steps at `rows` rows, under the scans' `spans` (each the
    exclusive end of its steps and the prefix they read) with `touched`
    held experts an expert layer a step."""
    d, t = section["hidden_size"], section["trajectory"]
    pattern = section["hybrid_override_pattern"]
    mamba, experts, attention = (pattern.count(c) for c in "ME*")
    heads, kv, hd = (section["num_attention_heads"],
                     section["num_key_value_heads"], section["head_dim"])
    h, n = section["mamba_num_heads"], section["ssm_state_size"]
    inner = h * section["mamba_head_dim"]
    channels = inner + 2 * section["n_groups"] * n
    steps = [hi - lo for lo, hi in zip((0, *spans), spans)]
    position = 2 * rows * kv * hd * 2  # keys and values of one position, bfloat16
    return {
        "mixers": 2 * mamba * (d * (inner + channels + h) + inner * d),
        "attention": 2 * attention * (2 * d * heads * hd + 2 * d * kv * hd),
        "shared": 2 * experts * 2 * d * section["moe_shared_expert_intermediate_size"],
        "routers": 4 * experts * d * section["router_width"],
        "experts": 2 * experts * touched * 2 * d * section["moe_intermediate_size"],
        "head": 2 * section["vocab_size"] * d,
        "state": 2 * 4 * mamba * rows * inner * n,  # float32, read and written
        "windows": 2 * 4 * mamba * rows * (section["conv_kernel"] - 1) * channels,
        "cache": attention * position
        * sum(k * span for k, span in zip(steps, spans)) / t}


def reduce(facts: dict, spec: dict):
    n, led = facts.get("trace_updates"), attribution_read.ledger(facts)
    section = facts.get("section", {})
    spans = facts.get("static", {}).get("decode_spans")
    touched = facts.get("counters", {}).get("held_experts_touched_mean")
    if (not n or led is None or not spans or touched is None
            or "hybrid_override_pattern" not in section):
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
        f"decode: {steps} steps move at least {size / 1e6:.1f} MB each "
        f"({', '.join(f'{k} {v / 1e6:.1f}' for k, v in parts.items())}), "
        f"{1e3 * size / peak:.3f} ms a step at HBM's peak; "
        f"{1e3 * seconds / steps:.3f} ms a step under "
        f"{spec['source_detail']['scopes']}")
    return 100.0 * size * steps / peak / seconds
