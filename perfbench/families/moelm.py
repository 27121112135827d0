"""Family `moelm`: a sparse-expert hybrid language model (configuration
`qwen3_next`: Qwen3-Next's three gated-delta-rule layers to one gated
attention layer, 2048 wide, in every layer a 512-way router over this
chip's 32 experts beside a shared expert, an 18,992-row slice of its
untied vocabulary) as the policy of the token-level IMPALA in the fused
loop `runtime/anakin_tokens.py`: what the mode `anakin_tokens_moe` and
`reducers/learn_mfu.py` ask of a family: operations per update from
shapes, and the comparisons with the plain reference
`references/qwen3_next.py` that decide `correct`. The distances'
arithmetic is `families/looplm.py`'s and the reference's optimizer step
leaf by leaf `families/hybridlm.py`'s, loaded from there; the limits,
the routing comparison and the replay are this file's.

ROUTING IS DISCONTINUOUS: a bfloat16 residual stream can swap a token's
tenth and eleventh expert. So the comparison has three parts, all told:
  (i)   the probabilities the program's router gave its chosen experts
        against the reference's for the same experts (`router_prob`,
        relative to the reference's largest);
  (ii)  the chosen sets against the reference's own, given the same sets
        upstream: `route_flip_share`, the share of (token, layer) whose
        set differs, under `ROUTING`'s `share`; and NONE may differ where
        the reference's margin (p_(10) - p_(11)) / p_(10) is over its
        `margin` (`flips_over_margin`, held at 0): a flip is a near tie
        or a fault;
  (iii) everything downstream against the reference run on the
        PROGRAM'S chosen sets, its weights w_i from the reference's own
        probabilities.

(a) `reference_check`, on a seeded batch of 2 x 1,024 tokens with an
    episode end inside a row (inside a chunk of the delta rule): (i),
    (ii), and logits, values, taken-action log-probabilities, the loss
    terms, the gradients' norm and the norm of one optimizer step's
    change, of the program (bfloat16 operands; the CHUNKED rule, the
    sorted pairs) and of a `highest` twin, against the float32 `highest`
    reference (the step-by-step recurrence, the experts in a loop).
(b) `chunk_check`, of what the COMPILED CHUNK THAT THE WINDOW DRIVES
    produced at the timed sizes (32 x 1,024): the reference replays the
    first warm chunk from the parameters it started from, on the
    update's own rollout, TWICE a row: once on the sets the decode steps
    chose (`act_routes`, which the chunk logs), against the log mu(a_t)
    collect wrote through the three kinds of state (all 32,768 steps)
    and the delta-rule state the episode ended with (a strided sample);
    once on the sets the learner chose (`routes`), against the logged
    loss terms, gradient norm and counters, and the parameters the chunk
    ended with against the reference's own RMSProp step. (ii) for both.

LIMITS. Every distance is relative to the reference's largest magnitude
of that quantity, except log-probabilities, which are held in nats. Each
limit lies between two readings (my chip runs, PR 36; PERF.md section
6): the largest the program gave over its seeds, and what a program in
the nearest precision below gives. `perfbench/tests/
test_qwen3_next_faults.py` plants each wrong program at a small size and
holds that `ok` comes out false.
"""

from __future__ import annotations

import functools
import os

import numpy as np

REFERENCE_ROWS = 2
STATE_SAMPLE = 16384  # `agents/moelm.py`'s: elements of the final state logged

# READINGS (my chip runs, PR 36; PERF.md section 6). "program": the range
# over fourteen seeds as timed (eight runs of the cell, six more of its
# second process's (a) alone); "bfloat16": what the plain reference reads
# computed in bfloat16 throughout (parameters, activations, state, router,
# softmax, loss: the nearest precision below the stated one) against
# itself in float32 on the sets the bfloat16 run chose, three seeds (a
# scratch script on the chip). A lower precision has to fail at least ONE
# limit, not each. TWO KINDS of statistic: what a PRECISION moves is held
# by a mean (`logits_rms`, `logp_mean`, `share`): these read the same to
# 3 % in every seed, and their limits lie between the two readings with a
# quarter of room or more on either side. The LARGEST element's distance
# (`logits`, `logp`, `logp_max_abs`) is what a fault in ONE place moves; it
# swings by a factor of two from seed to seed (one token with a large
# activation), so it is held at twice the largest reading and is NOT there
# to tell bfloat16 apart (a run whose `correct` is false refuses a PR).
#
# (ii) the chosen sets, the program against the reference given the same
# sets upstream. `share`, the share of (token, layer) whose set differs:
# program 0.164-0.178 | bfloat16 0.269-0.274: a sixth of the tokens has a
# tenth and an eleventh probability closer than the bfloat16 residual
# stream resolves (their typical gap is 3 % of p at this initialisation,
# the router's input is off by 1-2 %). `margin`: NO set may differ where
# the reference's (p_(10) - p_(11)) / p_(10) is over it: the largest
# margin at which the program's set differed is 0.051-0.067 over the 8,192
# (token, layer) of (a) and 0.071-0.075 over the 262,144 of (b) | bfloat16
# 0.086-0.101 over (a)'s: `margin` is over that too, so it is `share` that
# refuses bfloat16, and `margin` a fault that flips a set which is no near
# tie. The `highest` twin: 0 to 3 sets of 8,192 differ, at margins under
# 5e-5.
ROUTING = {"stated": {"share": 0.22, "margin": 0.12},
           "highest": {"share": 0.002, "margin": 0.001}}
# (a), the program as timed against the `highest` reference on the
# program's sets: the rounding to bfloat16 of every matmul operand and of
# the residual stream between 4 layers, each with a recurrence or an
# attention AND a sum over experts. program | bfloat16:
#   logits_rms 0.0177-0.0185 | 0.0289-0.0297
#   logp_mean  0.0127-0.0134 nats | 0.0263-0.0276
#   logits (largest) 0.019-0.039, logp (largest) 0.053-0.081 nats |
#          0.043-0.055, 0.109-0.125: held at twice the program's largest
#   value  0.0144-0.0202 | 0.029-0.034: granite's limit
#   router_prob 0.0156-0.0306 | 0.031-0.050: the readings touch (the
#          router's product is float32 in both; what differs is its input),
#          so the limit is twice the program's and holds the router's
#          arithmetic, not its precision
#   head_logp 9.5e-7 nats (the learner's head against the REFERENCE's
#          float32 log-softmax of the logits the program's plain head gave,
#          on the SAME hidden states: a second trace of the trunk chooses
#          other experts and read 0.0094) | a bfloat16 log-softmax 0.06
#          (granite's reading, PR 32: the same head)
#   update_norm at most 5.8e-6 | 0.913-0.928: a step of 1e-8 a weight is
#          under bfloat16 parameters' last bit
#   loss at most 0.0017, grad_norm 0.0011: the precision hardly moves them
#          (bfloat16 reads 0.0027 / 0.0031), so they take the limits of
#          `families/looplm.py`, 13 and 55 times the reading.
# bfloat16 is refused by `update_norm`, `logits_rms`, `logp_mean` and `share`.
STATED = {"router_prob": 0.06, "logits_rms": 0.023, "logits": 0.08,
          "value": 0.06, "logp_mean": 0.019, "logp": 0.16, "head_logp": 1e-4,
          "loss": 0.022, "grad_norm": 0.06, "update_norm": 1e-4}
# (a), the `highest` twin: the CHUNKED rule against the step-by-step
# recurrence and the sorted pairs against the loop over experts, the same
# arithmetic in another order; what is left is float32 rounding. Largest
# over the fourteen seeds: logits 2.2e-5, value 1.9e-5, router_prob 2.2e-5,
# logp 6.5e-5 nats, head_logp 9.5e-7, update_norm 8.3e-7; loss and
# grad_norm read 1e-6 to 3e-6 in thirteen seeds and 4.4e-5 / 1.9e-5 in one,
# so their limits stand 4.5 times over THAT. What reads over: every wrong
# program of the faults test, three orders over at a small size; a
# bfloat16 state across chunks (granite's read logits 8.4e-5 where its twin
# read 1.1e-5; this cell's was not measured on the chip).
HIGHEST = {"router_prob": 6e-5, "logits": 6e-5, "value": 5e-5, "logp": 1.5e-4,
           "head_logp": 1e-4, "loss": 2e-4, "grad_norm": 1e-4,
           "update_norm": 1e-5}
# (b), the compiled chunk against the reference's replay of it (one
# update a chunk), on the sets the decode steps and the learner chose.
# Range over eight runs | what reads over the limit:
#   logp_mean_abs 0.0124-0.0126 nats: the decode step rounds where the
#          reference's forward does not | bfloat16: not measured in (b); by
#          (a)'s proportion 0.026
#   logp_max_abs 0.062-0.0745 nats: the largest, at twice the reading | at
#          a small size on the CPU (the faults test): a window shifted by one
#   state 0.0117-0.0132: the delta-rule state the episode ended with, a
#          strided sample of 16,384, in the 2-norm over the reference's
#          (`state_max`, told and not held: 0.0075-0.022) | a state not
#          reset, a missing decay (the faults test); a bfloat16 act-time
#          state is refused by its BYTES (the mode), as granite's
#   beta_mean at most 1.3e-5 | beta missing 1.0 (the faults test)
#   grad_norm at most 0.0079 | learning half of the batch (the faults
#          test): `families/looplm.py`'s limit, 23 times the reading
#   step 0.022-0.039: the chunk's parameters after its optimizer step
#          against the reference's, over the norm of the reference's change
#          (1.5e-4) | no step at all 1.0, `p - u` 2.0
#   the LOSS terms read 0.0011-0.0104 of the summed policy-gradient terms:
#          `families/looplm.py`'s 0.019 would leave the largest under twice
#          of room where the rule asks for three, so (b) does not hold the
#          loss (`loss_told`); (a) does, and `grad_norm` and `step` refuse
#          here what a wrong loss would move.
CHUNK = {"logp_max_abs": 0.15, "logp_mean_abs": 0.018, "state": 0.03,
         "beta_mean": 1e-3, "grad_norm": 0.18, "step": 0.15}
LOSS_TERMS = ("total_loss", "pi_loss", "baseline_loss", "entropy")
COUNTERS = ("beta_mean", "decay_min", "router_entropy", "shared_gate_mean",
            "held_pair_share", "dropped_pairs", "experts_untouched",
            "expert_load_max_over_mean")
LOGGED = (*LOSS_TERMS, "grad_norm", *COUNTERS, "state_sample", "routes",
          "act_routes")


def _harness_dir() -> str:
    import childlib

    return os.path.dirname(os.path.abspath(childlib.__file__))


@functools.lru_cache(maxsize=None)
def reference_module():
    """`perfbench/references/qwen3_next.py`, beside the harness (the
    reference is yardstick, not data). Loaded once: its jitted pieces
    then compile once for both comparisons."""
    import discover

    return discover.module(_harness_dir(), "references", "qwen3_next")


@functools.lru_cache(maxsize=None)
def hybridlm():
    """`families/hybridlm.py`: the reference's optimizer step leaf by
    leaf, the program's side of (a), and through it `families/looplm.py`."""
    import discover

    return discover.module(_harness_dir(), "families", "hybridlm")


def looplm():
    return hybridlm().looplm()


# -- operations per update, from shapes ----------------------------------------

def forward_flops_per_token(section: dict) -> int:
    """One token through the learner's forward: every layer's matmuls
    (mixer projections, the router, the shared expert), the routed
    experts at the EXPECTED `num_experts_per_tok x num_experts /
    router_width` held experts a token (0.625 here; the chunk's
    `held_pair_share` says what a run really had), the chunked delta
    rule's products as it computes them (per chunk of C: k k^T and q k^T
    `[C, C]` a head, the solve's K + V right-hand sides, W S_0, q S_0,
    the read-out and the state's update), attention's q k^T and p v over
    the mean causal length, and the untied head with the value."""
    d, t = section["hidden_size"], section["trajectory"]
    hk, hv = section["linear_num_key_heads"], section["linear_num_value_heads"]
    dk, dv = section["linear_key_head_dim"], section["linear_value_head_dim"]
    heads, kv, hd = (section["num_attention_heads"],
                     section["num_key_value_heads"], section["head_dim"])
    f, s = section["moe_intermediate_size"], section["shared_expert_intermediate_size"]
    c = 64  # `agents/moelm.py` `gdn_chunk`
    held = (section["num_experts_per_tok"] * section["num_experts"]
            / section["router_width"])
    moe = (2 * d * section["router_width"] + 2 * 3 * d * s + 2 * d
           + held * 2 * 3 * d * f)
    delta = (2 * d * (2 * hk * dk + 2 * hv * dv + 2 * hv) + 2 * hv * dv * d
             # per token and value head: two [C]-rows of k k^T / q k^T, one
             # row of the solve (C / 2 of K + V columns), w S_0 and q S_0,
             # the read-out over u, the state's update
             + hv * (2 * 2 * c * dk + c * (dk + dv) + 2 * 2 * dk * dv
                     + 2 * c * dv + 2 * dk * dv))
    attention = (2 * d * (2 * heads * hd + 2 * kv * hd) + 2 * heads * hd * d
                 + 2 * 2 * (t + 1) * heads * hd // 2)
    kinds = section["layer_types"]
    return int(kinds.count("linear_attention") * (delta + moe)
               + kinds.count("full_attention") * (attention + moe)
               + 2 * d * (section["vocab_size"] + 1))


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
                rotary_dim=int(cfg.head_dim * cfg.partial_rotary_factor),
                rope_theta=cfg.rope_theta, gdn_key_heads=cfg.linear_num_key_heads,
                gdn_value_heads=cfg.linear_num_value_heads,
                gdn_key_dim=cfg.linear_key_head_dim,
                gdn_value_dim=cfg.linear_value_head_dim,
                top_k=cfg.num_experts_per_tok, first_expert=cfg.first_expert,
                experts_held=cfg.num_experts, rms_eps=cfg.rms_norm_eps,
                layer_order=tuple(cfg.layer_types),
                discount=cfg.discount_factor,
                baseline_loss_coef=cfg.baseline_loss_coef,
                entropy_coef=cfg.entropy_coef,
                reward_clipping=cfg.reward_clipping,
                gradient_clip_norm=cfg.gradient_clip_norm,
                learning_rate=cfg.start_learning_rate,
                end_learning_rate=cfg.end_learning_rate,
                learning_frame=cfg.learning_frame)


def seeded_batch(section: dict, rows: int, seed: int) -> dict:
    """`families/looplm.py`'s seeded batch (tokens and actions over the
    whole slice of the vocabulary, rho cut for about half of the steps,
    an episode's end inside row 0: here inside a chunk of the rule)."""
    return looplm().seeded_batch(section, rows, seed)


def perturbed(params, seed: int):
    """The parameters with norm scales, the bias and `dt_bias` moved off
    their initial 0 and 1 (which every precision represents exactly)."""
    import jax

    key = jax.random.PRNGKey(seed % (2 ** 31))
    moved = ("norms", "final_norm", "b_value", "q_norm", "k_norm", "gate_norm",
             "dt_bias")
    count = [0]

    def move(path, x):
        if path[-1].key not in moved:
            return x
        count[0] += 1
        return x + 0.1 * jax.random.normal(jax.random.fold_in(key, count[0]),
                                           x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(move, params)


def routing_facts(routing: dict, routes: np.ndarray) -> dict:
    """What one reference forward on the sets `routes [layers, B, T,
    top_k]` says of them: its own probabilities of the chosen experts,
    where its own sets differ, and its relative margin there."""
    probs = np.asarray(routing["probs"])  # [layers, B, T, E]
    picked = np.take_along_axis(probs, np.asarray(routes, np.int64), axis=-1)
    ranked = -np.sort(-probs, axis=-1)
    k = routes.shape[-1]
    return {"picked": picked,
            "flip": ~np.asarray(routing["same_set"]).astype(bool),
            "margin": (ranked[..., k - 1] - ranked[..., k])
            / np.maximum(ranked[..., k - 1], 1e-30)}


def route_distances(facts: list, side: str = "stated") -> dict:
    """(ii) over every reference forward made (`routing_facts`), under
    `ROUTING[side]`."""
    flip = np.concatenate([f["flip"].reshape(-1) for f in facts])
    margin = np.concatenate([f["margin"].reshape(-1) for f in facts])
    return {"route_flip_share": float(flip.mean()),
            "flips_over_margin": int(np.sum(
                flip & (margin > ROUTING[side]["margin"]))),
            "flip_margin_max": float(margin[flip].max()) if flip.any() else 0.0,
            "limits": ROUTING[side]}


def routes_ok(dist: dict) -> bool:
    return (dist["route_flip_share"] <= dist["limits"]["share"]
            and dist["flips_over_margin"] == 0)


def reference_sums(ref, theirs, batch: dict, hp: dict, routes, precision="highest",
                   logits: bool = True):
    """The reference's loss terms, per-step outputs and gradients of
    `batch` on the sets `routes [layers, rows, T, top_k]`, a row at a
    time -> (terms: sums over rows, `value`, `logp`, `states` and the
    routing facts with every row, `logits` only if asked (2.5 GB at 32
    rows); gradients as float32 leaves summed over the rows)."""
    import jax

    lm = looplm()
    rows = batch["tokens"].shape[0]
    sums = dict.fromkeys((*LOSS_TERMS, "pi_scale"), 0.0)
    per_row = {k: [] for k in ("logits", "value", "logp", "states", "routing")}
    counters = {k: [] for k in ("beta_mean", "decay_min", "router_entropy",
                                "shared_gate_mean", "held_pair_share")}
    acc = None
    for i in range(rows):
        terms, grads = ref.loss_and_grads(
            theirs, {k: v[i:i + 1] for k, v in batch.items()}, hp, precision,
            routes[:, i:i + 1])
        for k in sums:
            sums[k] += float(terms[k])
        for k in counters:
            counters[k].append(float(terms[k]))
        for k in ("logits", "value", "logp") if logits else ("value", "logp"):
            per_row[k].append(np.asarray(terms[k], np.float32))
        per_row["states"].append([np.asarray(s, np.float32)
                                  for s in terms["states"]])
        per_row["routing"].append(routing_facts(terms["routing"],
                                                routes[:, i:i + 1]))
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
    out.update({k: float(np.min(v) if k == "decay_min" else np.mean(v))
                for k, v in counters.items()})
    out["grad_norm"] = float(np.sqrt(sum(
        float(jax.numpy.sum(jax.numpy.square(g))) for g in acc)))
    return out, acc


def program_outputs(agent, params, nb: dict, precision=None) -> dict:
    """The program's own forward, loss terms, gradient norm, the sets it
    chose with their probabilities, and the norm of the parameters'
    change in one step of its optimizer, through `agent._loss`,
    `agent.tx` and the model's methods (`families/hybridlm.py`'s two
    jitted calls, the gradients donated to the second)."""
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents import common
    from distributed_reinforcement_learning_tpu.agents.looplm import LoopLMBatch

    model = agent.model

    def forward(p, b):
        grads, metrics = jax.grad(agent._loss, has_aux=True)(p, b)
        hs, counters = model.apply(p, b.tokens, b.done, method=model.trunk)
        logits, _, value = model.apply(p, hs, method=model.logits)
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1),
            b.action[None, ..., None], axis=-1)[..., 0]
        # The learner's head on the SAME hidden states: a second trace of
        # the trunk is compiled on its own, rounds elsewhere and so CHOOSES
        # other experts for some tokens (my chip run, PR 36: 0.0094 nats
        # where granite's second trunk agrees to 1e-6).
        stats = model.apply(p, hs, jnp.broadcast_to(b.action, hs.shape[:-1]),
                            method=model.token_stats)
        return grads, {"logits": logits, "value": value, "logp": logp,
                       "stats_logp": stats["logp"],
                       "grad_norm": common.global_norm(grads),
                       "routes": counters["routes"],
                       "route_probs": counters["route_probs"],
                       **{k: metrics[k] for k in LOSS_TERMS}}

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


def _rms(got, want) -> float:
    """The root of the mean squared distance over the reference's root
    mean square: what a precision moves, where the largest element's
    distance (`_rel`) is what a fault in one place moves."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean(np.square(got - want)))
                 / max(1e-30, np.sqrt(np.mean(np.square(want)))))


def _mean_abs(got, want) -> float:
    return float(np.mean(np.abs(np.asarray(got, np.float64)
                                - np.asarray(want, np.float64))))


def distances(got: dict, want: dict, action=None) -> dict:
    """Each quantity's largest distance from the reference computed on
    the program's sets: relative to the reference's largest magnitude;
    `logp` in nats; `head_logp`: the learner's blocked head against the
    REFERENCE's float32 log-softmax of the logits the program's plain
    head gave (the trunk's rounding cancels); `router_prob`: (i)."""
    lm = looplm()
    out = {k: lm._rel(got[k], want[k])
           for k in ("logits", "value", "grad_norm", "update_norm")}
    out["router_prob"] = lm._rel(got["route_probs"], want["routing"]["picked"])
    out["logp"] = lm._nats(got["logp"], want["logp"])
    out["logits_rms"], out["logp_mean"] = _rms(got["logits"], want["logits"]), \
        _mean_abs(got["logp"], want["logp"])
    if action is not None:
        out["head_logp"] = lm._nats(got["stats_logp"], reference_module().logp_of(
            got["logits"], np.broadcast_to(action, got["stats_logp"].shape)))
    out["loss"] = lm._loss_distance(got, want)
    return out


def reference_check(agent, train_state, section: dict, seed: int,
                    hp: dict | None = None) -> dict:
    """Comparison (a) of the module's docstring. `hp` is the
    configuration's (what `agent` was built from, unless a test plants a
    fault in `agent`). `train_state` is CONSUMED: the program's two sides
    run first, then its parameters make room for the reference's, which
    is run once for each side, on that side's chosen sets."""
    import jax

    hy, lm = hybridlm(), looplm()
    ref = reference_module()
    hp = hp or hyper(agent)
    params = perturbed(train_state.params, seed)
    for x in jax.tree.leaves(train_state.opt_state):
        getattr(x, "delete", lambda: None)()
    del train_state
    nb = seeded_batch(section, REFERENCE_ROWS, seed)
    got = {}
    for name, prog, precision in (("stated", agent, None),
                                  ("highest", lm.highest_twin(agent), "highest")):
        got[name] = program_outputs(prog, params, nb, precision)
        jax.clear_caches()  # the executable's scratch, before the next one
    theirs = ref.rekey(params, hp["layer_order"])
    hy.consume(params, theirs)
    del params
    out = {"ok": True, "limits": {"stated": STATED, "highest": HIGHEST},
           "distance": {}, "routing": {}}
    for name, limits in (("stated", STATED), ("highest", HIGHEST)):
        routes = np.asarray(got[name]["routes"])
        want, grads = reference_sums(ref, theirs, nb, hp, routes)
        _, _, want["update_norm"], _ = hy.reference_step(
            ref, theirs, None, grads, hp, 0, want["grad_norm"], keep=False)
        del grads
        dist = distances(got[name], want, nb["action"])
        routing = route_distances([want["routing"]], name)
        out["distance"][name], out["routing"][name] = dist, routing
        out["ok"] = out["ok"] and lm.within(dist, limits) and routes_ok(routing)
        out["reference"] = {"loss": float(want["total_loss"]),
                            "grad_norm": float(want["grad_norm"]),
                            "update_norm": float(want["update_norm"])}
    return out


def param_sample(params) -> list:
    return looplm().param_sample(params)


def chunk_record(before: list, after: list, metrics: dict) -> dict:
    """What comparison (b) replays, as flat numpy arrays (an `.npz`):
    `param_sample` of the parameters a chunk started from and ended
    with, and that chunk's own stacked metrics: the `[U, N, T]` rollout
    of every update and what each update logged, the sets the decode
    steps and the learner chose among it."""
    out = {f"before_{i}": a for i, a in enumerate(before)}
    out.update({f"after_{i}": a for i, a in enumerate(after)})
    out.update({f"rollout_{k}": np.asarray(v)
                for k, v in metrics["rollout"].items()})
    out.update({f"logged_{k}": np.asarray(metrics[k]) for k in LOGGED})
    return out


def state_sample(states: list) -> np.ndarray:
    """`agents/moelm.py` `state_counters`' strided sample, of the
    reference's final states (`[N, H, K, V]` a linear-attention layer)."""
    every = max(1, sum(s.size for s in states) // STATE_SAMPLE)
    return np.concatenate([s.reshape(-1)[::every] for s in states])


def acting_replay(ref, theirs, rollout: dict, hp: dict, act_routes) -> dict:
    """The reference's full forward of the update's rollout on the sets
    the DECODE steps chose (`act_routes [N, T, layers, top_k]`), a row at
    a time -> log pi(a_t) `[N, T]`, the final states, the routing facts."""
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
                 for k in ("probs", "same_set")}
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

    hy, lm = hybridlm(), looplm()
    ref = reference_module()
    hp = hyper(agent)
    leaves = jax.tree.leaves(params)
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
        theirs, nu, _, bits = hy.reference_step(ref, theirs, nu, grads, hp, u,
                                                want["grad_norm"])
        del grads
        nu = jax.device_get(nu) if u + 1 < updates else None
        if u == 0:  # in the program's layout and order of leaves
            last_bit = [float(np.max(x)) for x in
                        jax.tree.leaves(ref.stacked(bits))]
        diff = np.abs(rollout["behaviour_logp"].astype(np.float64) - acted["logp"])
        theirs_state = state_sample(acted["states"])
        here = {"loss_told": lm._loss_distance(got, want),
                "grad_norm": lm._rel(got["grad_norm"], want["grad_norm"]),
                "beta_mean": lm._rel(got["beta_mean"], want["beta_mean"]),
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
    flat = lambda sample: np.concatenate(
        [np.asarray(a, np.float64).reshape(-1) for a in sample])
    after = flat([record[f"after_{i}"] for i in range(len(leaves))])
    theirs_after = flat(param_sample(ref.stacked(theirs)))
    moved = theirs_after - flat(before)
    dist["step"] = float(np.linalg.norm(after - theirs_after)
                         / max(1e-30, np.linalg.norm(moved)))
    routing = route_distances(routings)
    return {"ok": (lm.within(dist, CHUNK) and routes_ok(routing)
                   and "pairs" not in dist),
            "distance": dist, "limits": CHUNK, "routing": routing,
            "counters_program_reference": counters,
            "updates": updates, "steps": int(diff.size) * updates,
            "reference": told, "reference_moved": float(np.linalg.norm(moved)),
            # leaf by leaf, the reference's own first step over float32's
            # spacing at the parameter: a leaf under 1 everywhere cannot be
            # told from one that stays (the mode reads this)
            "step_over_last_bit": last_bit}
