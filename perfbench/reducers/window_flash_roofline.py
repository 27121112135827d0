"""The flash kernels' share of the chip's bf16 peak in the learner of a
stack with TWO kinds of attention layer (sliding-window and global): the
operations one update's attention-kernel calls need, counted from shapes
over the VISIBLE (query, key) pairs (`visible_pairs`: W (W + 1) / 2 + (T -
W) W a head a row for a window layer of window W, T (T + 1) / 2 for a
global one; an episode is one unroll, so no pair is cut by an episode's
end), times `pair_flops` (the form of `reducers/mla_flash_roofline.py`:
forward, rematerialised forward, dq, dkv), times the updates of the
traced interval, over the traced device self time of the ops whose
`op_name` matches `source_detail.pattern` (the Mosaic calls under the
window and the global layers' scopes) and the published peak. The count is
the WORK, whatever implements the skip: a kernel that walks the blocks
outside the window takes longer for the same count and reads lower. The
kernels multiply p by v and ds by k as float32 operands, which the MXU
runs in several bf16 passes: against the bf16 peak that reads low. None
where no such op was traced (a program without the scopes, no profile)."""

import re

import peaks
import scope_read


def pair_flops(qk: int, v: int) -> int:
    """Per visible (query, key) pair, q/k `qk` wide and v `v` wide: forward
    q k^T + p v; the same again rematerialised; dq: q k^T, do v^T, ds k;
    dkv: q k^T, p^T do, do v^T, ds^T q."""
    return 2 * (2 * (qk + v) + (2 * qk + v) + (2 * qk + 2 * v))


def visible_pairs(t: int, window: int | None) -> int:
    """(query, key) pairs of one head of one row of `t` positions: key j
    visible to query i iff j <= i and, under a window, i - j < window."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def kernel_flops_per_update(section: dict, batch: int) -> dict:
    """{kind: operations} of every layer's attention kernels over `batch`
    episodes of `trajectory` tokens, the key/value heads repeated to the
    query heads as the program hands them to the kernels."""
    t, w = section["trajectory"], section["sliding_window_size"]
    layout = list(section["sliding_window_layout"])
    per_pair = (batch * section["num_attention_heads"]
                * pair_flops(section["head_dim"], section["head_dim"]))
    return {"window": layout.count(1) * visible_pairs(t, w) * per_pair,
            "global": layout.count(0) * visible_pairs(t, None) * per_pair}


def reduce(facts: dict, spec: dict):
    rows, n = scope_read.hlo_stats(facts), facts.get("trace_updates")
    section = facts.get("section", {})
    if not rows or not n or "sliding_window_layout" not in section:
        return None
    rx = re.compile(spec["source_detail"]["pattern"])
    seconds = {"window": 0.0, "global": 0.0}
    for _hlo, op_path, self_us in rows:
        m = rx.search(op_path)
        if m:
            seconds[m.group(1)] += self_us / 1e6
    if not sum(seconds.values()):
        return None
    peak = peaks.device_peaks(facts["device"]["kind"])["bf16_flops_per_s"]
    batch = facts.get("learn_batch") or (section["envs_per_actor"]
                                         * section["num_actors"])
    work = kernel_flops_per_update(section, batch)
    facts.setdefault("notes", []).append(
        "attention kernels an update: " + "; ".join(
            f"{kind} {1e3 * seconds[kind] / n:.2f} ms for "
            f"{work[kind] / 1e12:.2f} TFLOP over the visible pairs"
            for kind in seconds))
    return 100.0 * sum(work.values()) * n / (
        sum(seconds.values()) * facts["chips"] * peak)
