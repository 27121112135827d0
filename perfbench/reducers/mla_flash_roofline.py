"""The latent-attention flash kernels' share of the chip's bf16 peak in
the learner: the operations one update's attention-kernel calls need,
counted CAUSAL from shapes (`kernel_flops_per_update`), times the updates
of the traced interval, over the traced device self time of the ops
whose `op_name` matches `source_detail.pattern` (the Mosaic calls under
`.../mla/attend/pallas_call`: forward, rematerialised forward, dq and
dkv of every layer and of the prediction module) and the published
peak. The kernels multiply p by v and ds by k as float32 operands, which
the MXU runs in several bf16 passes: against the bf16 peak that reads
low, and says so. None where no such op was traced (a program without
the kernels, no profile)."""

import re

import peaks
import scope_read


def pair_flops(qk: int, v: int) -> int:
    """Per (query, key) pair that the causal mask lets through, q/k `qk`
    wide and v `v` wide: forward q k^T + p v; the same again
    rematerialised; dq: q k^T, do v^T, ds k; dkv: q k^T, p^T do, do v^T,
    ds^T q."""
    return 2 * (2 * (qk + v) + (2 * qk + v) + (2 * qk + 2 * v))


def kernel_flops_per_update(section: dict, batch: int) -> int:
    """Every layer's (the prediction module's too) attention over `batch`
    episodes of `trajectory` tokens, T (T + 1) / 2 pairs a head a row."""
    t = section["trajectory"]
    qk = section["qk_nope_head_dim"] + section["qk_rope_head_dim"]
    layers = section["num_hidden_layers"] + section["num_nextn_predict_layers"]
    return (layers * batch * section["num_attention_heads"] * (t * (t + 1) // 2)
            * pair_flops(qk, section["v_head_dim"]))


def reduce(facts: dict, spec: dict):
    rows, n = scope_read.hlo_stats(facts), facts.get("trace_updates")
    section = facts.get("section", {})
    if not rows or not n or "qk_nope_head_dim" not in section:
        return None
    rx = re.compile(spec["source_detail"]["pattern"])
    seconds = sum(self_us for _hlo, op_path, self_us in rows
                  if rx.search(op_path)) / 1e6
    if not seconds:
        return None
    peak = peaks.device_peaks(facts["device"]["kind"])["bf16_flops_per_s"]
    batch = facts.get("learn_batch") or (section["envs_per_actor"]
                                         * section["num_actors"])
    work = kernel_flops_per_update(section, batch) * n
    facts.setdefault("notes", []).append(
        f"latent-attention kernels: {1e3 * seconds / n:.2f} ms an update for "
        f"{work / n / 1e12:.2f} TFLOP counted causal")
    return 100.0 * work / (seconds * facts["chips"] * peak)
