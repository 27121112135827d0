"""Family `convlm`: a gated-short-convolution sparse-expert language model
(configuration `lfm2_moe`: LFM2-24B-A2B's double-gated short convolution
in three mixers of four and grouped-query attention in the fourth, 2048
wide, a dense leading layer and four expert layers with a sigmoid-scored
bias-corrected 64-way router over this chip's 16 experts and NO shared
expert, a 16,384-row slice of its tied vocabulary) as the policy of the
token-level IMPALA in the fused loop `runtime/anakin_tokens.py`: what the
mode `anakin_tokens_conv` and `reducers/learn_mfu.py` ask of a family:
operations per update from shapes, and the comparisons with the plain
reference `references/lfm2_moe.py` that decide `correct`.

WHAT IS LOADED AND WHAT IS HERE. `families/moelm.py` is loaded afresh as
this family's own copy (`moelm()`), with this file's limits, keys,
reference and the pieces that differ bound in the place of its own, as
`families/mlalm.py` does. From it, as it stands (the names of `SHARED`):
comparison (a)'s procedure (`reference_check`), part (ii) of the routing
comparison (`route_distances`, `routes_ok`), `chunk_record`, the seeded
batch, the parameters' sample, and through it `families/hybridlm.py` (the
reference's optimizer step leaf by leaf) and `families/looplm.py` (the
distances' arithmetic). Here: the operations, the limits with their
readings, and what this model has: the margin on s + b, the selection
bias (moved off zero in (a), held EQUAL in (b), its leaves kept out of
the optimizer's step), the router's counts over all experts, the gates'
mean magnitude, and a replay through nothing but the reference's full
forward in the place of the windows and the cache.

ROUTING IS DISCONTINUOUS (`families/moelm.py` says why), so the
comparison has that file's three parts, with the margin taken on the
BIASED scores s + b, which is what selects:
  (i)   the unbiased scores the program's router gave its chosen experts
        against the reference's for the same experts (`router_prob`);
  (ii)  the chosen sets against the reference's own, given the same sets
        upstream: `route_flip_share` under `ROUTING`'s `share`, and NONE
        may differ where the reference's margin ((s + b)_(4) - (s +
        b)_(5)) / (s + b)_(4) is over its `margin`;
  (iii) everything downstream against the reference run on the
        PROGRAM'S chosen sets, its weights from the reference's own scores.

(a) `reference_check`, on a seeded batch of 2 x 1,024 tokens with an
    episode end inside a row and a NON-ZERO selection bias: (i), (ii),
    and logits, values, taken-action log-probabilities, the loss terms,
    the gradients' norm and the norm of one optimizer step's change, of
    the program (bfloat16 operands and gated inputs; the flash kernels,
    the sorted pairs) and of a `highest` twin, against the float32
    `highest` reference (three shifted products, a dense masked softmax,
    the experts in a loop).
(b) `chunk_check`, of what the COMPILED CHUNK THAT THE WINDOW DRIVES
    produced at the timed sizes (64 x 1,024): the reference replays the
    first warm chunk from the parameters it started from, on the
    update's own rollout, TWICE a row: once on the sets the decode steps
    chose (`act_routes`), against the log mu(a_t) that collect wrote
    THROUGH THE WINDOWS AND THE CACHE (all 65,536 steps); once on the
    sets the learner chose (`routes`), against the logged loss terms,
    gradient norm and counters, the bias after the step (EQUAL: it moves
    by +-gamma from integer counts) and the parameters the chunk ended
    with against the reference's own RMSProp step.

LIMITS. Every distance is relative to the reference's largest magnitude
of that quantity, except log-probabilities, which are held in nats. Each
limit lies between two readings (my chip runs, PR 46; PERF.md section
6): the largest the program gave over its seeds, and what a program in
the nearest precision below gives.
`perfbench/tests/test_lfm2_moe_faults.py` plants each wrong program at a
small size and holds that `ok` comes out false.
"""

from __future__ import annotations

import functools
import os

import numpy as np

REFERENCE_ROWS = 2

# READINGS (my chip runs, PR 46; PERF.md section 6 has the table).
# "program": the range over the seeds as timed (nineteen of (a): the
# cell's nine runs and its second process's (a) alone on ten seeds of its
# own; nine of (b)); "bfloat16": what the plain reference reads computed in bfloat16
# throughout (parameters, activations, router, softmax, loss: the nearest
# precision below the stated one) against itself in float32 on the sets
# the bfloat16 run chose, two seeds
# (`perfbench/tests/test_lfm2_moe_control.py`, through this file's own
# comparison, on the chip at the cell's size). A lower precision has to
# fail at least ONE limit, not each. As in `families/moelm.py`: what a
# PRECISION moves is held by a mean (`logits_rms`, `logp_mean`, `share`),
# which reads the same to a few % in every seed, and its limit is the
# geometric mean of the two readings; the LARGEST element's distance
# (`logits`, `value`, `logp`, `router_prob`, `logp_max_abs`) is what a
# fault in ONE place moves; over 2 x 1,024 x 16,384 logits it swings by a
# factor of six with the seed (one token with a large activation), so it
# is held at about twice the largest reading and is NOT there to tell
# bfloat16 apart (its bfloat16 reading lies INSIDE the program's range: a
# uniformly rounded run has no one large element).
#
# (ii) `share`, the share of (token, layer) whose set differs: program
# 0.0414-0.0490 in (a), 0.0463-0.0471 in (b) | bfloat16 0.0897-0.0905: one
# set in twenty-two has a fourth and a fifth biased score closer than the
# bfloat16 residual stream resolves; the limit is the two readings'
# geometric mean. `margin`: NO set may differ where the reference's
# ((s + b)_(4) - (s + b)_(5)) / (s + b)_(4) is over it: the largest margin
# at which the program's set differed is 0.0063-0.0128 over the 8,192
# (token, layer) of (a) and 0.0137-0.0178 over the 524,288 of (b) |
# bfloat16 0.0141-0.0172 over (a)'s: `margin` is over that too (2.2 times
# the largest of either), so it is
# `share` that refuses bfloat16, and `margin` a fault that flips a set
# which is no near tie. The `highest` twin: ONE set of 8,192 differed on
# one seed of nineteen, none on the others.
ROUTING = {"stated": {"share": 0.066, "margin": 0.04},
           "highest": {"share": 0.002, "margin": 0.001}}
# (a), the program as timed against the `highest` reference on the
# program's sets. program | bfloat16:
#   logp_mean  0.00905-0.00974 nats | 0.0211-0.0216: geometric mean
#   logits_rms 0.01284-0.01332 | 0.0186-0.0187: 40 % apart (`joyai_flash`'s
#          nearly touch and are not held: this model's learner has no
#          flash kernel in four layers of five), so it is HELD: geometric mean
#   logits (largest) 0.0124-0.0835, value 0.0109-0.0471, logp 0.0384-0.0861
#          nats | 0.019-0.021, 0.018-0.023, 0.087: twice the program's largest
#   router_prob 0.0065-0.0089 | 0.0102-0.0117: sigmoid scores of a float32
#          `highest` product in both; what differs is the router's input;
#          the readings nearly touch, a largest element again: twice the
#          program's, which holds the router's arithmetic, not its precision
#   head_logp 9.5e-7 nats | 0.048 (a bfloat16 log-softmax)
#   update_norm 5.9e-6-9.0e-5 (it swings by a factor of fifteen with the
#          seed) | 0.937-0.938: a step of 1e-8 a weight is under bfloat16
#          parameters' last bit, and a state left unchanged reads 1. The
#          limit is the geometric mean of the largest reading and 1, the
#          more room above the reading since fresh seeds read higher
#   loss 2.5e-5-1.8e-3, grad_norm 3.9e-6-1.1e-3 | 5.9e-4-2.5e-3, 2.8e-4-
#          2.0e-3: the precision hardly moves them, so they take the limits
#          of `families/looplm.py`, twelve and fifty times the reading.
#   load: the router's counts over all 64 experts, which move the bias,
#          on the program's sets: EQUAL (integers).
# bfloat16 is refused by `update_norm`, `logp_mean`, `logits_rms`,
# `head_logp` and `share`.
STATED = {"router_prob": 0.018, "logits_rms": 0.0157, "logits": 0.17,
          "value": 0.095, "logp_mean": 0.014, "logp": 0.18, "head_logp": 1e-4,
          "loss": 0.022, "grad_norm": 0.06, "update_norm": 1e-2, "load": 0}
# (a), the `highest` twin: dense attention and the sorted pairs against
# the dense masked softmax and the loop over experts, the taps as a sum
# over a padded array against three shifted products: the same arithmetic
# in another order; what is left is float32 rounding. Largest over the
# seeds: logits 1.09e-6, value 1.29e-6, router_prob 8.4e-7, logp 9.5e-6
# nats, head_logp 9.5e-7, loss 2.9e-6, grad_norm 5.8e-7, update_norm
# 6.2e-7 (an order under the two other expert cells' twins: a sum of three
# taps reorders less than a chunked recurrence or 2,048 keys). Every limit
# stands ten to thirty times over its largest reading; every wrong program
# of the faults test reads three orders over at a small size.
HIGHEST = {"router_prob": 2e-5, "logits": 2e-5, "value": 2e-5, "logp": 1e-4,
           "head_logp": 1e-4, "loss": 5e-5, "grad_norm": 1e-5,
           "update_norm": 1e-5, "load": 0}
# (b), the compiled chunk against the reference's replay of it (one
# update a chunk), on the sets the decode steps and the learner chose.
#   logp_mean_abs 0.00937-0.00943 nats: the decode step rounds the gated
#          input into its window and the keys into the cache where the
#          reference's forward does not | bfloat16 0.0211-0.0216: (a)'s
#          measured pair, the same statistic on the seeded batch; it is
#          (a)'s pair that holds this limit, the control was not read on
#          a rollout
#   logp_max_abs 0.050-0.057 nats: the largest, at 2.7 times the
#          reading | a window read in another order, a window not shifted,
#          a state not reset (the faults test)
#   conv_gate 2.0e-6-3.3e-6: the mean |B| and |C| of the four convolution
#          layers' gates in the learner's forward, relative: float32
#          rounding of a mean over 1.07e9 values | a gate left out reads
#          0.3 and more (the faults test); thirty times the reading
#   grad_norm 0.0010-0.0019 | `families/looplm.py`'s limit
#   step 0.0105-0.0128: the chunk's parameters after its optimizer step
#          against the reference's, over the norm of the reference's
#          change (2.3e-4) | no step at all 1.0, `p - u` 2.0: the geometric
#          mean of the largest reading and 1
#   the bias after the step and the counts over all 64 experts: EQUAL
#   the LOSS terms read 0.0003-0.0059 of the summed policy-gradient terms
#          (`loss_told`, told and not held, as in `families/moelm.py`).
CHUNK = {"logp_max_abs": 0.15, "logp_mean_abs": 0.014, "conv_gate": 1e-4,
         "grad_norm": 0.18, "step": 0.11}
LOSS_TERMS = ("total_loss", "pi_loss", "baseline_loss", "entropy")
COUNTERS = ("router_score_mean", "held_pair_share", "conv_gate_abs_mean",
            "dropped_pairs", "experts_untouched", "expert_load_max_over_mean",
            "router_load_max_over_mean", "pair_slabs_mean", "pair_slabs_max",
            "bias_abs_max", "conv_state_abs_max")
LOGGED = (*LOSS_TERMS, "grad_norm", *COUNTERS, "router_load", "routes",
          "act_routes")


def _harness_dir() -> str:
    import childlib

    return os.path.dirname(os.path.abspath(childlib.__file__))


@functools.lru_cache(maxsize=None)
def reference_module():
    """`perfbench/references/lfm2_moe.py`, beside the harness (the
    reference is yardstick, not data). Loaded once: its jitted pieces
    then compile once for both comparisons."""
    import discover

    return discover.module(_harness_dir(), "references", "lfm2_moe")


# `families/moelm.py`'s, as they stand, on this file's limits and pieces.
SHARED = ("hybridlm", "looplm", "seeded_batch", "param_sample", "reference_check",
          "route_distances", "routes_ok", "chunk_record")
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
    mod.moe_distances = mod.distances
    for name in OWN:
        setattr(mod, name, globals()[name])
    return mod


def __getattr__(name: str):
    if name in SHARED:
        return getattr(moelm(), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# -- operations per update, from shapes ----------------------------------------

def forward_flops_per_token(section: dict) -> int:
    """One token through the learner's forward: every layer's matmuls (a
    short convolution's in- and out-projection and its three taps, or
    the attention's q, k, v and o with q k^T and p v over the mean CAUSAL
    length; the dense MLP, or the router and the routed experts at the
    EXPECTED `num_experts_per_tok x num_experts / router_width` held
    experts a token: 1.0 here; the chunk's `held_pair_share` says what a
    run really had; there is no shared expert) and the tied head with the
    value."""
    d, t = section["hidden_size"], section["trajectory"]
    heads, kv = section["num_attention_heads"], section["num_key_value_heads"]
    hd = d // heads
    mixer = {"conv": 2 * (d * 3 * d + d * d) + 2 * section["conv_L_cache"] * d,
             "full_attention": 2 * (2 * d * heads * hd + 2 * d * kv * hd)
             + 2 * 2 * (t + 1) * heads * hd // 2}
    held = (section["num_experts_per_tok"] * section["num_experts"]
            / section["router_width"])
    dense = 2 * 3 * d * section["intermediate_size"]
    moe = (2 * d * section["router_width"]
           + held * 2 * 3 * d * section["moe_intermediate_size"])
    layers = sum(mixer[kind] + (dense if i < section["num_dense_layers"] else moe)
                 for i, kind in enumerate(section["layer_types"]))
    return int(layers + 2 * d * (section["vocab_size"] + 1))


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
                rope_theta=cfg.rope_theta, top_k=cfg.num_experts_per_tok,
                first_expert=cfg.first_expert, experts_held=cfg.num_experts,
                route_scale=cfg.routed_scaling_factor, rms_eps=cfg.norm_eps,
                layer_order=tuple(
                    f"{mixer}+{'dense' if i < cfg.num_dense_layers else 'moe'}"
                    for i, mixer in enumerate(cfg.layer_types)),
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
    """The parameters with norm scales and the value bias moved off their
    initial 1 and 0 (which every precision represents exactly), and the
    selection bias off zero by 0.01 (ten of its steps: the bias then
    changes some sets, and a router that selects by the unbiased scores
    or weighs by the biased ones is seen)."""
    import jax

    key = jax.random.PRNGKey(seed % (2 ** 31))
    moved = {"norms": 0.1, "final_norm": 0.1, "b_value": 0.1, "q_norm": 0.1,
             "k_norm": 0.1, "router_bias": 0.01}
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


def reference_sums(ref, theirs, batch: dict, hp: dict, routes, precision="highest",
                   logits: bool = True):
    """The reference's loss terms, per-step outputs and gradients of
    `batch` on the sets `routes [layers, rows, T, top_k]` (None: on the
    sets it chooses itself, returned as `routes`), a row at a time ->
    (terms: sums over rows, `value`, `logp` and the routing facts with
    every row, `logits` only if asked; gradients as float32 leaves summed
    over the rows)."""
    import jax

    lm = moelm().looplm()
    rows = batch["tokens"].shape[0]
    sums = dict.fromkeys((*LOSS_TERMS, "pi_scale"), 0.0)
    per_row = {k: [] for k in ("logits", "value", "logp", "routing")}
    means = {k: [] for k in ("held_pair_share", "router_score_mean",
                             "conv_gate_abs_mean")}
    load, acc = 0, None
    for i in range(rows):
        chosen = None if routes is None else routes[:, i:i + 1]
        terms, grads = ref.loss_and_grads(
            theirs, {k: v[i:i + 1] for k, v in batch.items()}, hp, precision, chosen)
        if chosen is None:
            chosen = np.asarray(terms["routing"]["chosen"])
        for k in sums:
            sums[k] += float(terms[k])
        for k in means:
            means[k].append(float(terms[k]))
        load = load + np.asarray(terms["router_load"], np.int64)
        for k in ("logits", "value", "logp") if logits else ("value", "logp"):
            per_row[k].append(np.asarray(terms[k], np.float32))
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
    out["routing"] = {k: np.concatenate([r[k] for r in per_row["routing"]], axis=1)
                      for k in per_row["routing"][0]}
    out.update({k: float(np.mean(v)) for k, v in means.items()}, router_load=load)
    out["grad_norm"] = float(np.sqrt(sum(
        float(jax.numpy.sum(jax.numpy.square(g))) for g in acc)))
    return out, acc


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


def distances(got: dict, want: dict, action=None) -> dict:
    """`families/moelm.py`'s distances from the reference computed on the
    program's sets ((i) on the sigmoid scores), and `load`, the largest
    difference in the router's counts over all experts."""
    out = moelm().moe_distances({**got, "route_probs": got["route_scores"]}, want,
                                action)
    out["load"] = float(np.max(np.abs(
        np.asarray(got["router_load"], np.int64) - want["router_load"])))
    return out


def bias_leaves(params) -> list:
    """The indices, among the leaves of the program's parameters, of the
    routers' selection biases."""
    import jax

    return [i for i, (path, _) in enumerate(
        jax.tree_util.tree_leaves_with_path(params))
        if path[-1].key == "router_bias"]


def acting_replay(ref, theirs, rollout: dict, hp: dict, act_routes) -> dict:
    """The reference's full forward of the update's rollout on the sets
    the DECODE steps chose (`act_routes [N, T, layers, top_k]`), a row at
    a time -> log pi(a_t) `[N, T]` and the routing facts."""
    import jax

    routes = np.moveaxis(np.asarray(act_routes), 2, 0)  # [layers, N, T, k]
    logp, routing = [], []
    for i in range(rollout["tokens"].shape[0]):
        with jax.default_matmul_precision("highest"):
            out = ref.forward(theirs, rollout["tokens"][i:i + 1],
                              rollout["done"][i:i + 1], hp,
                              routes=routes[:, i:i + 1])
            logp.append(np.asarray(ref.logp_of(out["logits"][0],
                                               rollout["action"][i:i + 1])))
        facts = {k: np.stack([np.asarray(r[k]) for r in out["routing"]])
                 for k in ("probs", "same_set", "margin", "edge")}
        routing.append(routing_facts(facts, routes[:, i:i + 1]))
        del out, facts
    return {"logp": np.concatenate(logp),
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
    dist = dict.fromkeys((*CHUNK, "loss_told"), 0.0)  # the last: told, not held
    nu, told, routings, counters = None, [], [], {}
    for u in range(updates):
        rollout = {k: v[u] for k, v in rollouts.items()}
        got = {k: record[f"logged_{k}"][u] for k in LOGGED}
        acted = acting_replay(ref, theirs, rollout, hp, got["act_routes"])
        want, grads = reference_sums(ref, theirs, rollout, hp,
                                     np.asarray(got["routes"]), logits=False)
        theirs, nu, _, bits = hy.reference_step(ref, theirs, nu, grads, hp, u,
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
        here = {"loss_told": lm._loss_distance(got, want),
                "grad_norm": lm._rel(got["grad_norm"], want["grad_norm"]),
                "conv_gate": lm._rel(got["conv_gate_abs_mean"],
                                     want["conv_gate_abs_mean"]),
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
            # leaf by leaf, the reference's own first step over float32's
            # spacing at the parameter: a leaf under 1 everywhere cannot be
            # told from one that stays (the mode reads this)
            "step_over_last_bit": last_bit}
