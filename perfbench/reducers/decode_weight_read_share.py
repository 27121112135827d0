"""The decode steps' share of their roofline: a decode step at a small
batch is bound by the bytes of the weights it reads, so the least time
the chip could take for the traced decode steps is `act_weight_bytes x
steps / the published HBM bytes a second`, held against the device
seconds that the program's own attribution (`attribution_read.ledger`)
places under `source_detail.scopes` (the ledger's `collect/act` and
below, by every rule: what `*_decode_resolved_ms_per_update` reads).

The bytes are counted HERE, from the configuration's shapes: every
matrix a decode step reads WHOLE, in bfloat16 (2 B): each layer's mixer
(a short convolution's in- and out-projection, or the attention's q, k, v
and o), its MLP (the dense SwiGLU, or the router and the HELD experts'
gate, up and down) and the vocabulary head. NOT counted: the embedding's
gathered rows, the taps, the norms, the key/value cache, the windows or
any activation (and the router at 2 B where the program reads it in
float32). So it is a lower bound on what travels, and the share can only
read low. None without a profile, or for a section whose mixers this
count does not know."""

import attribution_read
import peaks
from scope_read import _under

MIXERS = ("conv", "full_attention")


def act_weight_bytes(section: dict) -> int:
    """bfloat16 bytes of every matrix one decode step reads whole."""
    d = section["hidden_size"]
    head = d // section["num_attention_heads"]
    mixer = {"conv": d * 3 * d + d * d,
             "full_attention": 2 * d * d + 2 * d * section["num_key_value_heads"] * head}
    dense = 3 * d * section["intermediate_size"]
    experts = (d * section["router_width"]
               + section["num_experts"] * 3 * d * section["moe_intermediate_size"])
    count = section["vocab_size"] * d
    for i, kind in enumerate(section["layer_types"]):
        count += mixer[kind] + (dense if i < section["num_dense_layers"] else experts)
    return 2 * count


def reduce(facts: dict, spec: dict):
    n, led = facts.get("trace_updates"), attribution_read.ledger(facts)
    section = facts.get("section", {})
    if not n or led is None or not set(section.get("layer_types", ("?",))) <= set(MIXERS):
        return None
    seconds = sum(s for scope, s in led["scopes"].items()
                  if _under(scope, spec["source_detail"]["scopes"]))
    if not seconds:
        return None
    peak = peaks.device_peaks(facts["device"]["kind"])["hbm_bytes_per_s"]
    size, steps = act_weight_bytes(section), n * section["trajectory"]
    facts.setdefault("notes", []).append(
        f"decode: {steps} steps read {size} B of weights each, at least "
        f"{1e3 * size / peak:.3f} ms a step; {1e3 * seconds / steps:.3f} ms a step "
        f"under {spec['source_detail']['scopes']}")
    return 100.0 * size * steps / peak / seconds
