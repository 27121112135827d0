"""Family `ssmoelm`: a state-space / sparse-expert / attention language
model whose layers are ONE sublayer each (configuration `nemotron_h_moe`:
Nemotron-3-Nano-30B-A3B's layers 0-8 at 2688 wide, `MEMEM*EME`: four
Mamba-2 mixers of 64 heads in 8 B/C groups, four sigmoid-scored
bias-corrected 128-way routers over this chip's 8 UNGATED relu^2 experts
beside one shared expert, one NoPE attention layer of 32 / 2 heads of 128,
a 16,384-row slice of its untied vocabulary) as the policy of the
token-level IMPALA in the fused loop `runtime/anakin_tokens.py`: what the
mode `anakin_tokens_ssmoe` and `reducers/learn_mfu.py` ask of a family:
operations per update from shapes, and the comparisons with the plain
reference `references/nemotron_h_moe.py` that decide `correct`.

WHAT IS LOADED AND WHAT IS HERE. `families/moelm.py` is loaded afresh as
this family's own copy (`moelm()`), DIRECTLY (ROADMAP D16), with this
file's limits, keys, reference and the pieces that differ bound in the
place of its own, as `families/convlm.py` does. From it, as it stands (the
names of `SHARED`): comparison (a)'s procedure (its `reference_check`,
under this file's of the same name, which adds the seconds), part (ii) of
the routing comparison (`route_distances`, `routes_ok`), `chunk_record`,
the seeded batch, the parameters' sample, `state_sample`, and through it
`families/hybridlm.py` (`consume`) and `families/looplm.py` (the
distances' arithmetic, the `highest` twin: float32 with dense attention,
which 2 x 2,048 positions of 32 heads allow). Here: the operations, the
limits with their readings, the margin on s + b, the selection bias (moved
off zero in (a), held EQUAL in (b), its leaves kept out of the optimizer's
step), the router's counts over all 128 experts, the share of
up-projections that ReLU zeroes, the recurrent state an episode ends with,
and a replay through nothing but the reference's full forward in the
place of the recurrent states, the windows, the cache and the record;
and, because a whole run of the cell has to end inside the driver's 360 s,
`families/swalm.py`'s two repairs of the second process: `distances`
reduces what reads the logits on the device, and `reference_step` runs a
leaf of the reference's optimizer step as one compiled call (`SECONDS`
tells where the second process's time went, part by part).

ROUTING IS DISCONTINUOUS (`families/moelm.py` says why), so the
comparison has that file's three parts, with the margin taken on the
BIASED scores s + b, which is what selects:
  (i)   the unbiased scores the program's router gave its chosen experts
        against the reference's for the same experts (`router_prob`);
  (ii)  the chosen sets against the reference's own, given the same sets
        upstream: `route_flip_share` under `ROUTING`'s `share`, and NONE
        may differ where the reference's margin ((s + b)_(6) - (s +
        b)_(7)) / (s + b)_(6) is over its `margin`;
  (iii) everything downstream against the reference run on the
        PROGRAM'S chosen sets, its weights from the reference's own scores.

(a) `reference_check`, on a seeded batch of 2 x 2,048 tokens with an
    episode end inside a chunk of the scan (step 682 = 5 x 128 + 42) and a
    NON-ZERO selection bias: (i), (ii), and logits, values, taken-action
    log-probabilities, the loss terms, the gradients' norm and the norm of
    one optimizer step's change, of the program (bfloat16 operands; the
    chunked scan by group, the flash kernels, the sorted pairs) and of a
    `highest` twin, against the float32 `highest` reference (the
    step-by-step recurrence, the grouped norm written out, a dense masked
    softmax, the experts in a loop).
(b) `chunk_check`, of what the COMPILED CHUNK THAT THE WINDOW DRIVES
    produced at the timed sizes (16 x 2,048): the reference replays the
    first warm chunk from the parameters it started from, on the update's
    own rollout, TWICE a row: once on the sets the decode steps chose
    (`act_routes`), against the log mu(a_t) that collect wrote THROUGH THE
    RECURRENT STATES, THE WINDOWS, THE CACHE AND THE RECORD (all 32,768
    steps) and the recurrent state the episode ended with (a strided
    sample); once on the sets the learner chose (`routes`), against the
    logged loss terms, gradient norm and counters, the bias after the step
    (EQUAL: it moves by +-gamma from integer counts) and the parameters
    the chunk ended with against the reference's own RMSProp step.

LIMITS. Every distance is relative to the reference's largest magnitude
of that quantity, except log-probabilities, which are held in nats. Each
limit lies between two readings ON THE CHIP AT THE CELL'S SIZE (my chip
runs, PR 53; PERF.md section 6 has the table): the largest the program
gave over its seeds, and what the plain reference gives computed in
bfloat16 throughout (`perfbench/tests/test_nemotron_h_moe_control.py`).
`perfbench/tests/test_nemotron_h_moe_faults.py` plants each wrong program
at a small size and holds that `ok` comes out false.
"""

from __future__ import annotations

import contextlib
import functools
import os
import time

import numpy as np

REFERENCE_ROWS = 2

# READINGS (my chip runs, PR 53, chip calls 1-4 and the final call 6; PERF.md
# section 6 has the table). "program": the range over the seeds as timed
# (three of (a) and of (b) when the limits were set; the final calls' are in
# PERF.md); "bfloat16": what the plain reference reads computed in bfloat16
# throughout (parameters, activations, recurrent state, router, softmax,
# loss: the nearest precision below the stated one) against itself in
# float32 on the sets the bfloat16 run chose, two seeds
# (`perfbench/tests/test_nemotron_h_moe_control.py`, through this file's own
# comparison, on the chip at the cell's size). A lower precision has to fail
# at least ONE limit, not each. As in `families/moelm.py`: what a PRECISION
# moves is held by a mean (`logp_mean`, `share`), which reads the same to a
# few % in every seed, and its limit is the geometric mean of the two
# readings; the LARGEST element's distance (`logits`, `value`, `logp`,
# `router_prob`, `logp_max_abs`) is what a fault in ONE place moves; over 2
# x 2,048 x 16,384 logits it swings with the seed (one token with a large
# activation), so it is held at three times the largest of three readings
# and is NOT there to tell bfloat16 apart (its bfloat16 reading lies UNDER
# the program's range: a uniformly rounded run has no one large element).
#
# The final tree's seven seeds (chip call 6) read inside every limit: value
# 0.082-0.199, logits 0.151-0.219, logp 0.32-0.64 nats, router_prob 0.0050-
# 0.0060, logp_mean 0.0096-0.0105, share 0.0513-0.0559; (b) state 0.0115-
# 0.0137, dt_mean 8.6e-6-2.5e-4, relu2_zero 6e-6-2.3e-5, grad_norm 0.045-0.065.
#
# (ii) `share`, the share of (token, layer) whose set differs: program
# 0.0533-0.0555 in (a), 0.0541-0.0547 in (b) | bfloat16 0.1278-0.1310: one
# set in eighteen has a sixth and a seventh biased score closer than the
# bfloat16 residual stream resolves; the limit is the two readings'
# geometric mean. `margin`: NO set may differ where the reference's ((s +
# b)_(6) - (s + b)_(7)) / (s + b)_(6) is over it: the largest margin at
# which the program's set differed is 0.0059-0.0072 over the 16,384 (token,
# layer) of (a) and 0.0079-0.0104 over the 131,072 of (b) | bfloat16
# 0.0122-0.0135 over (a)'s: `margin` is 2.2 times the largest of either, so
# it is `share` that refuses bfloat16, and `margin` a fault that flips a set
# which is no near tie. The `highest` twin: 1 to 3 sets of 16,384 differed
# (share 0.00006-0.00018), at margins under 1e-5.
ROUTING = {"stated": {"share": 0.084, "margin": 0.03},
           "highest": {"share": 0.002, "margin": 0.001}}
# (a), the program as timed against the `highest` reference on the
# program's sets. program | bfloat16:
#   logp_mean  0.00986-0.01051 nats | 0.02045-0.02072: geometric mean
#   logits_rms 0.0178-0.0192 | 0.0147-0.0148: the bfloat16 reference reads
#          UNDER the program (the program's chunked scan and its kernels
#          round where the step-by-step reference in bfloat16 does not):
#          TOLD in the line, not held (as `families/mlalm.py`'s)
#   value 0.110-0.177, logits (largest) 0.167-0.185, logp (largest)
#          0.386-0.525 nats | 0.0175-0.0184, 0.0149-0.0169, 0.081-0.086:
#          largest elements: three times the program's largest; a backstop
#          that a gross fault meets (the small-size plants read 0.5 and
#          more), NOT BETWEEN TWO READINGS: PERF.md names them as told
#   router_prob 0.0053-0.0058 | 0.0087-0.0100: sigmoid scores of a float32
#          `highest` product in both; what differs is the router's input;
#          a largest element again: three times the program's largest,
#          which holds the router's arithmetic, not its precision
#   head_logp 2e-6-5e-6 nats | 0.0487-0.0493 (a bfloat16 log-softmax)
#   update_norm 1e-6-1.2e-5 | 0.800-0.820: a step of 1e-8 a weight is under
#          bfloat16 parameters' last bit, and a state left unchanged reads
#          1. The limit is the other expert cells' 1e-2, over the geometric
#          mean of the largest reading and 1 (3.5e-3): the more room above
#          the reading since fresh seeds read higher
#   loss 8e-5-4.0e-4, grad_norm 1.2e-4-5.9e-4 | 6.5e-4-1.3e-3, 5.2e-4-
#          2.3e-3: the precision hardly moves them, so they take the limits
#          of `families/looplm.py`, 55 and 100 times the program's largest
#   load: the router's counts over all 128 experts, which move the bias,
#          on the program's sets: EQUAL (integers).
# bfloat16 is refused by `update_norm`, `logp_mean`, `head_logp` and `share`.
STATED = {"router_prob": 0.017, "logits": 0.55, "value": 0.53,
          "logp_mean": 0.0147, "logp": 1.5, "head_logp": 1e-4,
          "loss": 0.022, "grad_norm": 0.06, "update_norm": 1e-2, "load": 0}
# (a), the `highest` twin: the chunked scan by group against the
# step-by-step recurrence (16 chunks of 128 against 2,048 steps), dense
# attention and the sorted pairs against the dense masked softmax and the
# loop over experts: the same arithmetic in another order; what is left is
# float32 rounding. Largest over three seeds: value 3.9e-5, logits 4.1e-5,
# router_prob 1.7e-5, logp 9.4e-5 nats, head_logp 4.8e-6, loss 2.0e-6,
# grad_norm 1.25e-6, update_norm 2.4e-7. Every limit stands five to forty
# times over its largest reading; every wrong program of the faults test
# reads orders over at a small size.
HIGHEST = {"router_prob": 1e-4, "logits": 2e-4, "value": 2e-4, "logp": 5e-4,
           "head_logp": 1e-4, "loss": 5e-5, "grad_norm": 1e-5,
           "update_norm": 1e-5, "load": 0}
# (b), the compiled chunk against the reference's replay of it (one
# update a chunk), on the sets the decode steps and the learner chose.
#   logp_mean_abs 0.00893-0.00907 nats: the decode step rounds the keys and
#          values into the cache and runs its products in bfloat16 where
#          the reference's forward does not | bfloat16 0.02045-0.02072:
#          (a)'s measured pair, the same statistic on the seeded batch; it
#          is (a)'s pair that holds this limit, the control was not read on
#          a rollout: geometric mean
#   logp_max_abs 0.046-0.0545 nats: the largest of 32,768 steps: three times
#          the largest | a state not carried, a window not shifted (the
#          faults test)
#   state 0.0123-0.0130: the strided sample of the recurrent states the
#          episode ended with, relative L2 (largest element 0.0116-0.0117,
#          told as `state_max`): bfloat16 operands in front of a float32
#          state over 2,048 steps; three times the largest. A bfloat16
#          STATE cannot be told from it by accuracy (granite's finding, PR
#          32) and is refused by its bytes
#   relu2_zero 1.0e-6-7.6e-6: the share of the held pairs' up-projections
#          that ReLU zeroed in the learner's forward (0.5004), relative:
#          values whose sign the bfloat16 operands flip | `relu` for
#          `relu^2` does not move it, a gate matrix does; thirteen times
#          the largest
#   dt_mean 2.8e-6-1.55e-4: the state-space layers' mean step size in the
#          learner's forward (0.0319-0.0335), relative: bfloat16 operands of
#          the in-projection | a dt clamped to its initial range reads 0.02
#          and more (the faults test): thirteen times the largest
#   grad_norm 0.029-0.049 | `families/looplm.py`'s limit, 3.7 times
#   step 0.0077-0.034: the chunk's parameters after its optimizer step
#          against the reference's, over the norm of the reference's
#          change | no step at all 1.0, `p - u` 2.0: the geometric mean of
#          the largest reading and 1 is 0.18; 0.16 as `families/swalm.py`'s
#   the bias after the step and the counts over all 128 experts: EQUAL
#   the LOSS terms read 0.0036-0.025 of the summed policy-gradient terms
#          (`loss_told`, told and not held, as in `families/moelm.py`).
CHUNK = {"logp_max_abs": 0.16, "logp_mean_abs": 0.0136, "state": 0.04,
         "relu2_zero": 1e-4, "dt_mean": 2e-3, "grad_norm": 0.18, "step": 0.16}
LOSS_TERMS = ("total_loss", "pi_loss", "baseline_loss", "entropy")
COUNTERS = ("router_score_mean", "held_pair_share", "relu2_zero_share", "dt_mean",
            "dropped_pairs", "experts_untouched", "router_experts_untouched",
            "expert_load_max_over_mean", "router_load_max_over_mean",
            "pair_slabs_mean", "pair_slabs_max", "bias_abs_max", "state_norm_mean",
            "held_experts_touched_mean")
LOGGED = (*LOSS_TERMS, "grad_norm", *COUNTERS, "state_sample", "router_load",
          "routes", "act_routes")


# Where the second process's seconds go, by part (summed over calls):
# printed as each part ends and told under `seconds` in both results.
SECONDS: dict = {}


@contextlib.contextmanager
def _timed(part: str):
    t0 = time.time()
    try:
        yield
    finally:
        took = time.time() - t0
        SECONDS[part] = round(SECONDS.get(part, 0.0) + took, 2)
        print(f"[perfbench] ssmoelm check: {part} {took:.2f} s", flush=True)


def _timing(part: str):
    def wrap(fn):
        @functools.wraps(fn)
        def timed(*args, **kwargs):
            with _timed(part):
                return fn(*args, **kwargs)
        return timed
    return wrap


def _harness_dir() -> str:
    import childlib

    return os.path.dirname(os.path.abspath(childlib.__file__))


@functools.lru_cache(maxsize=None)
def reference_module():
    """`perfbench/references/nemotron_h_moe.py`, beside the harness (the
    reference is yardstick, not data). Loaded once: its jitted pieces
    then compile once for both comparisons."""
    import discover

    return discover.module(_harness_dir(), "references", "nemotron_h_moe")


# `families/moelm.py`'s, as they stand, on this file's limits and pieces.
SHARED = ("hybridlm", "looplm", "seeded_batch", "param_sample", "route_distances",
          "routes_ok", "chunk_record", "state_sample")
# This file's, bound into the copy in the place of that family's own.
OWN = ("ROUTING", "STATED", "HIGHEST", "LOGGED", "reference_module", "hyper",
       "perturbed", "program_outputs", "reference_sums", "distances")


@functools.lru_cache(maxsize=None)
def moelm():
    """`families/moelm.py` as THIS family's copy (`discover.module` makes
    a new module at every call; the copy that family's own cell runs is
    another), with the names of `OWN` bound to this file's."""
    import discover

    mod = discover.module(_harness_dir(), "families", "moelm")
    for name in OWN:
        setattr(mod, name, globals()[name])
    mod.hybridlm().reference_step = reference_step  # that copy's, loaded once
    return mod


def __getattr__(name: str):
    if name in SHARED:
        return getattr(moelm(), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# -- operations per update, from shapes ----------------------------------------

def forward_flops_per_token(section: dict) -> int:
    """One token through the learner's forward, by the kind of each layer
    of `hybrid_override_pattern`: an `M` layer's in- and out-projection,
    its taps, and the chunked scan's four products at the chunk's length Q
    (the `C . B` scores once a GROUP: 2 G N Q / 2 causal on average is
    counted whole, Q, as the program computes the whole `[Q, Q]` block;
    scores x xdt 2 H P Q; the read of the past 2 H P N; the chunk's state
    2 H P N); a `*` layer's q, k, v and o with q k^T and p v over the mean
    CAUSAL length; an `E` layer's router, its shared expert (2 matrices)
    and the routed experts at the EXPECTED `num_experts_per_tok x
    n_routed_experts / router_width` held experts a token: 0.375 here (the
    chunk's `held_pair_share` says what a run really had); and the untied
    head with the value."""
    d, t = section["hidden_size"], section["trajectory"]
    pattern = section["hybrid_override_pattern"]
    heads, kv, hd = (section["num_attention_heads"],
                     section["num_key_value_heads"], section["head_dim"])
    h, p, n = (section["mamba_num_heads"], section["mamba_head_dim"],
               section["ssm_state_size"])
    g, q = section["n_groups"], min(section["chunk_size"], t)
    inner = h * p
    channels = inner + 2 * g * n
    held = (section["num_experts_per_tok"] * section["n_routed_experts"]
            / section["router_width"])
    layer = {
        "M": 2 * (d * (inner + channels + h) + inner * d)
        + 2 * section["conv_kernel"] * channels
        + 2 * g * n * q + 2 * inner * q + 2 * 2 * inner * n,
        "*": 2 * (2 * d * heads * hd + 2 * d * kv * hd)
        + 2 * 2 * (t + 1) * heads * hd // 2,
        "E": 2 * d * section["router_width"]
        + 2 * 2 * d * section["moe_shared_expert_intermediate_size"]
        + held * 2 * 2 * d * section["moe_intermediate_size"]}
    return int(sum(layer[c] for c in pattern) + 2 * d * (section["vocab_size"] + 1))


def learn_flops_per_update(section: dict, torso=None,
                           batch: int | None = None) -> int:
    """Forward + backward (3 x forward) over `batch` episodes of
    `trajectory` tokens. NOT counted, as in the other cells: the acting
    pass (T decode steps at batch N) and the rematerialised blocks.
    `torso` is not read: a token has no torso."""
    b = batch or section["envs_per_actor"] * section["num_actors"]
    return 3 * forward_flops_per_token(section) * b * section["trajectory"]


# -- the comparisons --------------------------------------------------------------


def hyper(agent) -> dict:
    cfg = agent.cfg
    return dict(num_heads=cfg.num_attention_heads,
                num_kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
                mamba_heads=cfg.mamba_num_heads, mamba_head_dim=cfg.mamba_head_dim,
                mamba_groups=cfg.n_groups, mamba_state=cfg.ssm_state_size,
                top_k=cfg.num_experts_per_tok, first_expert=cfg.first_expert,
                experts_held=cfg.n_routed_experts,
                route_scale=cfg.routed_scaling_factor,
                rms_eps=cfg.layer_norm_epsilon,
                layer_order=cfg.hybrid_override_pattern,
                discount=cfg.discount_factor,
                baseline_loss_coef=cfg.baseline_loss_coef,
                entropy_coef=cfg.entropy_coef,
                reward_clipping=cfg.reward_clipping,
                gradient_clip_norm=cfg.gradient_clip_norm,
                learning_rate=cfg.start_learning_rate,
                end_learning_rate=cfg.end_learning_rate,
                learning_frame=cfg.learning_frame,
                bias_update_speed=cfg.bias_update_speed)


def perturbed(params, seed: int):
    """The parameters with norm scales, D, the convolution's and the
    value's bias moved off their initial 1 and 0 (which every precision
    represents exactly), and the selection bias off zero by 0.01 (ten of
    its steps: the bias then changes some sets, and a router that selects
    by the unbiased scores or weighs by the biased ones is seen)."""
    import jax

    key = jax.random.PRNGKey(seed % (2 ** 31))
    moved = {"norms": 0.1, "final_norm": 0.1, "b_value": 0.1, "gate_norm": 0.1,
             "D": 0.1, "conv_b": 0.1, "router_bias": 0.01}
    count = [0]

    def move(path, x):
        if path[-1].key not in moved:
            return x
        count[0] += 1
        return x + moved[path[-1].key] * jax.random.normal(
            jax.random.fold_in(key, count[0]), x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(move, params)


def routing_facts(routing: dict, routes: np.ndarray) -> dict:
    """What one reference forward on the sets `routes [layers, B, T,
    top_k]` says of them: its own scores of the chosen experts, where
    its own sets differ, and its relative margin on s + b there."""
    probs = np.asarray(routing["probs"], np.float32)  # [layers, B, T, E]
    return {"picked": np.take_along_axis(probs, np.asarray(routes, np.int64), axis=-1),
            "flip": ~np.asarray(routing["same_set"]).astype(bool),
            "margin": np.asarray(routing["margin"], np.float64)
            / np.maximum(np.abs(np.asarray(routing["edge"], np.float64)), 1e-30)}


@_timing("reference_sums")
def reference_sums(ref, theirs, batch: dict, hp: dict, routes, precision="highest",
                   logits: bool = True):
    """The reference's loss terms, per-step outputs and gradients of
    `batch` on the sets `routes [layers, rows, T, top_k]` (None: on the
    sets it chooses itself, returned as `routes`), a row at a time ->
    (terms: sums over rows, `value`, `logp`, `states` and the routing
    facts with every row, `logits` only if asked; gradients as float32
    leaves summed over the rows)."""
    import jax

    lm = moelm().looplm()
    rows = batch["tokens"].shape[0]
    sums = dict.fromkeys((*LOSS_TERMS, "pi_scale"), 0.0)
    per_row = {k: [] for k in ("logits", "value", "logp", "states", "routing")}
    means = {k: [] for k in ("held_pair_share", "router_score_mean",
                             "relu2_zero_share", "dt_mean")}
    load, acc = 0, None
    for i in range(rows):
        chosen = None if routes is None else routes[:, i:i + 1]
        terms, grads = ref.loss_and_grads(
            theirs, {k: v[i:i + 1] for k, v in batch.items()}, hp, precision, chosen,
            logits)
        if chosen is None:
            chosen = np.asarray(terms["routing"]["chosen"])
        for k in sums:
            sums[k] += float(terms[k])
        for k in means:
            means[k].append(float(terms[k]))
        load = load + np.asarray(terms["router_load"], np.int64)
        for k in ("logits", "value", "logp") if logits else ("value", "logp"):
            per_row[k].append(np.asarray(terms[k], np.float32))
        per_row["states"].append([np.asarray(s, np.float32) for s in terms["states"]])
        per_row["routing"].append({**routing_facts(terms["routing"], chosen),
                                   "routes": chosen})
        del terms
        leaves = jax.tree.leaves(grads)
        del grads
        if acc is None:
            acc = leaves
        else:
            lm._add_into(acc, leaves)
    out = dict(sums)
    for k in ("logits", "value", "logp"):
        if per_row[k]:
            out[k] = np.concatenate(per_row[k], axis=1)  # [1, rows, T, ...]
    out["states"] = [np.concatenate([row[j] for row in per_row["states"]])
                     for j in range(len(per_row["states"][0]))]
    out["routing"] = {k: np.concatenate([r[k] for r in per_row["routing"]], axis=1)
                      for k in per_row["routing"][0]}
    # a row's tokens are as many as another's, its held pairs are not
    held = np.asarray(means["held_pair_share"], np.float64)
    out.update(held_pair_share=float(held.mean()), router_load=load,
               router_score_mean=float(np.mean(means["router_score_mean"])),
               dt_mean=float(np.mean(means["dt_mean"])),
               relu2_zero_share=float(np.sum(
                   held * means["relu2_zero_share"]) / max(held.sum(), 1e-30)))
    out["grad_norm"] = float(np.sqrt(sum(
        float(jax.numpy.sum(jax.numpy.square(g))) for g in acc)))
    return out, acc


@_timing("program_outputs")
def program_outputs(agent, params, nb: dict, precision=None) -> dict:
    """The program's own forward, loss terms, gradient norm, the sets it
    chose with their scores, and the norm of the parameters' change in
    one step of its optimizer, through `agent._loss`, `agent.tx` and the
    model's methods (two jitted calls, the gradients donated to the
    second)."""
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents import common
    from distributed_reinforcement_learning_tpu.agents.looplm import LoopLMBatch

    model = agent.model

    def forward(p, b):
        grads, metrics = jax.grad(agent._loss, has_aux=True)(p, b)
        hs, _ = model.apply(p, b.tokens, b.done, method=model.trunk)
        logits, _, value = model.apply(p, hs, method=model.logits)
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1),
            b.action[None, ..., None], axis=-1)[..., 0]
        # The learner's head on the SAME hidden states: a second trace of
        # the trunk rounds elsewhere and so CHOOSES other experts for some
        # tokens (`families/moelm.py`).
        stats = model.apply(p, hs, jnp.broadcast_to(b.action, hs.shape[:-1]),
                            method=model.token_stats)
        return grads, {"logits": logits, "value": value, "logp": logp,
                       "stats_logp": stats["logp"],
                       "grad_norm": common.global_norm(grads),
                       **{k: metrics[k] for k in (
                           *LOSS_TERMS, "routes", "route_scores", "router_load")}}

    def step(p, grads):
        updates, _ = agent.tx.update(grads, agent.tx.init(p), p)
        # The barrier: see `families/looplm.py` (the TPU compiler folds the
        # round trip through the parameters' dtype away without it).
        new = jax.lax.optimization_barrier(jax.tree.map(
            lambda x, u: (x + u).astype(x.dtype), p, updates))
        return common.global_norm(jax.tree.map(lambda y, x: y - x, new, p))

    def run():
        grads, out = jax.jit(forward)(params, LoopLMBatch(**nb))
        out["update_norm"] = jax.jit(step, donate_argnums=(1,))(params, grads)
        return jax.device_get(out)

    if precision is None:
        return run()
    with jax.default_matmul_precision(precision):
        return run()


@functools.lru_cache(maxsize=None)
def _leaf_step():
    """One leaf of the reference's optimizer step as ONE compiled call."""
    import jax
    import jax.numpy as jnp

    ref = reference_module()

    def leaf(p, nu, g, scale, lr):
        g = g * scale
        q, new = ref.rmsprop_leaf(p, nu, g, lr)
        return (q, new, ref.step_over_last_bit(p, nu, g, lr).reshape(1),
                jnp.sum(jnp.square((q - p).astype(jnp.float32))))

    return jax.jit(leaf, donate_argnums=(2,))


@_timing("reference_step")
def reference_step(ref, theirs, nu, grads: list, hp: dict, step: int,
                   grad_norm: float, keep: bool = True):
    """`families/hybridlm.reference_step`, to the letter of what it
    returns, with a leaf's arithmetic (the reference's own `rmsprop_leaf`
    and `step_over_last_bit`) under one `jax.jit` a leaf
    (`families/swalm.py`'s repair: op by op, the leaves' two dozen
    elementwise programs a shape took 11 s a step there)."""
    import jax

    scale = min(1.0, hp["gradient_clip_norm"] / max(grad_norm, 1e-30))
    lr = ref.learning_rate(step, hp)
    leaves, tree = jax.tree.flatten(theirs)
    nus = nu if nu is not None else [1.0] * len(leaves)
    new, new_nu, bits, moved = [], [], [], 0.0
    for i, (p, n) in enumerate(zip(leaves, nus)):
        g, grads[i] = grads[i], None
        q, n, bit, sq = _leaf_step()(p, n, g, scale, lr)
        moved += float(sq)
        if keep:
            new.append(q)
            new_nu.append(n)
            bits.append(bit)
    if not keep:
        return None, None, moved ** 0.5, None
    return (jax.tree.unflatten(tree, new), new_nu, moved ** 0.5,
            jax.tree.unflatten(tree, bits))


@functools.lru_cache(maxsize=None)
def _logit_row():
    """One row's logits `[T, V]`, the program's and the reference's, on
    the device -> (max |g - w|, max |w|, sum (g - w)^2, sum w^2, the
    reference's float32 log-softmax of the PROGRAM's logits at `action`)."""
    import jax
    import jax.numpy as jnp

    def row(got, want, action):
        diff = got - want
        return (jnp.max(jnp.abs(diff)), jnp.max(jnp.abs(want)),
                jnp.sum(jnp.square(diff)), jnp.sum(jnp.square(want)),
                reference_module().logp_of(got, action))

    return jax.jit(row)


@_timing("distances")
def distances(got: dict, want: dict, action=None) -> dict:
    """`families/moelm.py`'s distances from the reference computed on the
    program's sets ((i) on the sigmoid scores), and `load`, the largest
    difference in the router's counts over all experts. What reads the
    logits (`logits`, `logits_rms`, `head_logp`) is reduced ON THE DEVICE,
    a row at a time, in float32 (`families/swalm.py`'s repair)."""
    shared = moelm()
    lm = shared.looplm()
    out = {k: lm._rel(got[k], want[k])
           for k in ("value", "grad_norm", "update_norm")}
    out["router_prob"] = lm._rel(got["route_scores"], want["routing"]["picked"])
    out["logp"] = lm._nats(got["logp"], want["logp"])
    out["logp_mean"] = shared._mean_abs(got["logp"], want["logp"])
    vocab = np.shape(want["logits"])[-1]
    steps = np.shape(want["logits"])[-2]
    rows = lambda x: np.asarray(x, np.float32).reshape(-1, steps, vocab)
    stats = np.asarray(got["stats_logp"], np.float64).reshape(-1, steps)
    actions = (np.zeros(stats.shape, np.int32) if action is None else
               np.broadcast_to(action, np.shape(got["stats_logp"])).reshape(stats.shape))
    far = big = sq = ref_sq = head = 0.0
    for i, (g, w) in enumerate(zip(rows(got["logits"]), rows(want["logits"]))):
        d, m, s, r, logp = _logit_row()(g, w, actions[i])
        far, big = max(far, float(d)), max(big, float(m))
        sq, ref_sq = sq + float(s), ref_sq + float(r)
        head = max(head, float(np.max(np.abs(stats[i] - np.asarray(logp, np.float64)))))
    out["logits"] = far / max(1e-12, big)
    out["logits_rms"] = float(np.sqrt(sq) / max(1e-30, np.sqrt(ref_sq)))
    if action is not None:
        out["head_logp"] = head
    out["loss"] = lm._loss_distance(got, want)
    out["load"] = float(np.max(np.abs(
        np.asarray(got["router_load"], np.int64) - want["router_load"])))
    return out


def reference_check(*args, **kwargs) -> dict:
    """Comparison (a): `families/moelm.py`'s procedure on this file's
    pieces, with where its seconds went."""
    with _timed("reference_check"):
        out = moelm().reference_check(*args, **kwargs)
    return {**out, "seconds": dict(SECONDS)}


def bias_leaves(params) -> list:
    """The indices, among the leaves of the program's parameters, of the
    routers' selection biases."""
    import jax

    return [i for i, (path, _) in enumerate(
        jax.tree_util.tree_leaves_with_path(params))
        if path[-1].key == "router_bias"]


@_timing("acting_replay")
def acting_replay(ref, theirs, rollout: dict, hp: dict, act_routes) -> dict:
    """The reference's full forward of the update's rollout on the sets
    the DECODE steps chose (`act_routes [N, T, layers, top_k]`), a row at
    a time -> log pi(a_t) `[N, T]`, the recurrent states the episode ended
    with, the routing facts."""
    import jax

    routes = np.moveaxis(np.asarray(act_routes), 2, 0)  # [layers, N, T, k]
    logp, states, routing = [], [], []
    for i in range(rollout["tokens"].shape[0]):
        with jax.default_matmul_precision("highest"):
            out = ref.forward(theirs, rollout["tokens"][i:i + 1],
                              rollout["done"][i:i + 1], hp,
                              routes=routes[:, i:i + 1])
            logp.append(np.asarray(ref.logp_of(out["logits"][0],
                                               rollout["action"][i:i + 1])))
        states.append([np.asarray(s, np.float32) for s in out["states"]])
        facts = {k: np.stack([np.asarray(r[k]) for r in out["routing"]])
                 for k in ("probs", "same_set", "margin", "edge")}
        routing.append(routing_facts(facts, routes[:, i:i + 1]))
        del out, facts
    return {"logp": np.concatenate(logp),
            "states": [np.concatenate([row[j] for row in states])
                       for j in range(len(states[0]))],
            "routing": {k: np.concatenate([r[k] for r in routing], axis=1)
                        for k in routing[0]}}


def chunk_check(agent, params, record: dict) -> dict:
    """Comparison (b) of the module's docstring, under `agent`'s
    configuration. `params`: the parameters the recorded chunk started
    from, made anew from the seed; CONSUMED (their device buffers are
    freed once the reference has its own copy)."""
    import jax

    shared = moelm()
    hy, lm, param_sample = shared.hybridlm(), shared.looplm(), shared.param_sample
    ref = reference_module()
    hp = hyper(agent)
    leaves = jax.tree.leaves(params)
    biased = bias_leaves(params)
    before = [record[f"before_{i}"] for i in range(len(leaves))]
    if not all(np.array_equal(a, b)
               for a, b in zip(param_sample(params), before)):
        return {"ok": False, "why": "the parameters made anew from the seed "
                "are not those the recorded chunk started from"}
    theirs = ref.rekey(params, hp["layer_order"])
    hy.consume(params, theirs)
    rollouts = {k[len("rollout_"):]: v for k, v in record.items()
                if k.startswith("rollout_")}
    updates = rollouts["tokens"].shape[0]
    dist = dict.fromkeys((*CHUNK, "state_max", "loss_told"), 0.0)  # the last two: told, not held
    nu, told, routings, counters = None, [], [], {}
    for u in range(updates):
        rollout = {k: v[u] for k, v in rollouts.items()}
        got = {k: record[f"logged_{k}"][u] for k in LOGGED}
        acted = acting_replay(ref, theirs, rollout, hp, got["act_routes"])
        want, grads = reference_sums(ref, theirs, rollout, hp,
                                     np.asarray(got["routes"]), logits=False)
        theirs, nu, _, bits = reference_step(ref, theirs, nu, grads, hp, u,
                                             want["grad_norm"])
        theirs = ref.bias_step(theirs, want["router_load"], hp)
        del grads
        nu = jax.device_get(nu) if u + 1 < updates else None
        if u == 0:  # in the program's layout and order of leaves
            last_bit = [float(np.max(x)) for x in
                        jax.tree.leaves(ref.stacked(bits))]
            for i in biased:  # moved by gamma, not by the optimizer
                last_bit[i] = float("inf")
        diff = np.abs(rollout["behaviour_logp"].astype(np.float64) - acted["logp"])
        theirs_state = shared.state_sample(acted["states"])
        here = {"loss_told": lm._loss_distance(got, want),
                "grad_norm": lm._rel(got["grad_norm"], want["grad_norm"]),
                "relu2_zero": lm._rel(got["relu2_zero_share"],
                                      want["relu2_zero_share"]),
                "dt_mean": lm._rel(got["dt_mean"], want["dt_mean"]),
                "state": float(np.linalg.norm(got["state_sample"] - theirs_state)
                               / np.linalg.norm(theirs_state)),
                "state_max": lm._rel(got["state_sample"], theirs_state),
                "logp_max_abs": float(diff.max()),
                "logp_mean_abs": float(diff.mean())}
        dist.update({k: max(dist[k], v) for k, v in here.items()})
        routings += [acted["routing"], want["routing"]]
        told.append({"loss": want["total_loss"], "grad_norm": want["grad_norm"],
                     "logp_mean": float(acted["logp"].mean())})
        counters = {k: (float(got[k]), want.get(k)) for k in COUNTERS}
        if float(got["dropped_pairs"]) != 0 or abs(
                float(got["held_pair_share"]) - want["held_pair_share"]) > 1e-6:
            dist["pairs"] = float("inf")  # a pair dropped, or not counted
        if not np.array_equal(np.asarray(got["router_load"], np.int64),
                              want["router_load"]):
            dist["load"] = float("inf")  # the counts that move the bias
    flat = lambda sample: np.concatenate(
        [np.asarray(a, np.float64).reshape(-1) for a in sample])
    theirs_sample = param_sample(ref.stacked(theirs))
    # The bias is held EQUAL: it moves by +-gamma from integer counts.
    if not all(np.array_equal(record[f"after_{i}"], theirs_sample[i])
               for i in biased):
        dist["bias"] = float("inf")
    rest = [i for i in range(len(leaves)) if i not in biased]
    after = flat([record[f"after_{i}"] for i in rest])
    theirs_after = flat([theirs_sample[i] for i in rest])
    moved = theirs_after - flat([before[i] for i in rest])
    dist["step"] = float(np.linalg.norm(after - theirs_after)
                         / max(1e-30, np.linalg.norm(moved)))
    routing = shared.route_distances(routings)
    return {"ok": (lm.within(dist, CHUNK) and shared.routes_ok(routing)
                   and not {"pairs", "load", "bias"} & set(dist)),
            "distance": dist, "limits": CHUNK, "routing": routing,
            "counters_program_reference": counters,
            "updates": updates, "steps": int(diff.size) * updates,
            "reference": told, "reference_moved": float(np.linalg.norm(moved)),
            "seconds": dict(SECONDS),
            # leaf by leaf, the reference's own first step over float32's
            # spacing at the parameter: a leaf under 1 everywhere cannot be
            # told from one that stays (the mode reads this)
            "step_over_last_bit": last_bit}
