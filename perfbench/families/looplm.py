"""Family `looplm`: a looped language model (configuration
`ouro_looplm`: Ouro's 2048-wide decoder stack run four times with shared
weights, an exit gate, a 49,152-action head) as the policy of a
token-level IMPALA in the fused loop `runtime/anakin_tokens.py`: what
the mode `anakin_tokens` and `reducers/learn_mfu.py` ask of a family:
operations per update from shapes, and the two comparisons with the
plain reference `references/ouro_looplm.py` that decide `correct`.

(a) `reference_check`, on a seeded batch of 2 x 128 tokens with
    non-trivial actions, rewards, behaviour log-probabilities and an
    episode end inside a row: every pass's logits, gates, values and
    taken-action log-probabilities, the loss terms, the gradients' norm
    and the norm of the parameters' change in one optimizer step, of the
    program (bfloat16 operands, as the configuration states) and of a
    `highest` twin of it (float32 operands, `highest` matmuls, dense
    attention), against the float32 `highest` reference. It is a program
    of the check's own around `agent._loss` and `agent.tx`: what it is
    for is the precision (the twin, the limits between two precisions).
(b) `chunk_check`, of what the COMPILED CHUNK THAT THE WINDOW DRIVES
    itself produced, at the timed sizes (32 x 128, the four-block head):
    the reference replays the first warm chunk from the parameters it
    started from, update by update, on each update's own rollout. Held
    against it, for every update: the log mu(a_t) that collect wrote
    through the per-pass cache (all 4,096 steps), the loss terms, the
    exit entropy and the gradient norm that the update logged; and, over
    the chunk, the parameters it ended with (a strided sample of every
    leaf) against the reference's own optimizer steps.

LIMITS. Every distance is relative to the reference's largest magnitude
of that quantity, except log-probabilities, which are held in nats
(their difference IS log rho). Each limit lies between two readings (my
chip runs, PR 30; PERF.md section 6): the largest the program gave over
its seeds, and what the reference gives in the nearest precision below
the stated one (bfloat16 parameters, activations, softmax and loss:
`precision="bfloat16"`), which has to read over at least one of them.
`perfbench/tests/test_ouro_looplm_faults.py` plants each wrong program
and holds that `ok` comes out false.
"""

from __future__ import annotations

import os

import numpy as np

REFERENCE_ROWS = 2
CHUNK_BLOCK = 2  # rows a block of the reference's gradients in (b)
SAMPLE = 65536  # elements compared per parameter leaf

# (a), the program as timed against the `highest` reference. The
# distance is the rounding to bfloat16 of every matmul operand AND of the
# residual stream between 4 x 8 layer applications. Largest over 46
# readings (16 of scratch scripts, 30 runs of the cell; my chip runs, PR
# 30) | what reads over the limit:
#   logits 0.0220   | three passes instead of four 0.17-0.21
#   gate   0.0184   | three passes 0.106-0.139
#   value  0.0299   | three passes 0.148-0.262
#   logp   0.0597 nats | three passes 0.51-0.55
#   head_logp 9.5e-7 nats (the learner's blocked log-softmax against the
#          REFERENCE's float32 one of the logits the program's plain head
#          gave: the trunk's rounding cancels) | a bfloat16 log-softmax
#          0.047-0.049
#   update_norm 4.6e-6 (the clip at 40 is always on, so the step's norm is
#          lr x 40 / sqrt(1.09) whatever the gradient's direction) |
#          bfloat16 PARAMETERS 0.91-0.92, float16 1.0 (and an infinite
#          gradient norm), the bfloat16 reference 0.90-0.92: a step of
#          1.5e-8 a weight is under their last bit
#   loss 0.0072, grad_norm 0.0201 (under 0.012 in all but one: that
#          seed's gradient is carried by a few large components): the
#          precision hardly moves them (the bfloat16 reference reads
#          0.0036 / 0.0050), so their limits are three times the reading
#          alone.
# The reference in the nearest precision below (bfloat16 parameters,
# activations, softmax and loss) reads logits 0.016-0.017, gate
# 0.008-0.012, value 0.014-0.025, logp 0.040-0.069: the SAME as the
# program, whose residual stream is bfloat16 too; it is refused by
# `update_norm` alone, and a bfloat16 log-softmax by `head_logp` alone.
STATED = {"logits": 0.04, "gate": 0.04, "value": 0.06, "logp": 0.12,
          "head_logp": 1e-4, "loss": 0.022, "grad_norm": 0.06,
          "update_norm": 1e-4}
# (a), the `highest` twin against the same reference: the same
# arithmetic in another order (a scan, fused projections, a blocked
# head); what is left is float32 rounding. Largest over the 46: logits
# 1.02e-6, gate 1.03e-6, value 1.58e-6, logp 9.5e-6, head_logp 8.6e-6,
# loss 6.5e-6, grad_norm 7.6e-7, update_norm 4.6e-7; every wrong program
# above reads four to six orders over these.
HIGHEST = {"logits": 1e-5, "gate": 1e-5, "value": 1e-5, "logp": 1e-4,
           "head_logp": 1e-4, "loss": 5e-5, "grad_norm": 1e-5,
           "update_norm": 1e-5}
# (b), the compiled chunk against the reference's replay of it, the
# larger of the chunk's two updates. Largest over 21 chunks (15 runs of
# the cell, 6 of a scratch script; my chip runs, PR 30) | what reads over
# the limit:
#   logp_max_abs 0.0738 nats, logp_mean_abs 0.0127 (bfloat16 again: the
#          decode step rounds where the reference's forward does not) | a
#          cache SHARED between the passes 5.1-5.5 and 1.13-1.17, three
#          passes 0.68 and 0.14. A bfloat16 log-softmax at act time would
#          NOT read over these (it adds about 0.03 to a noise of 0.06), and
#          one in the learner passes this replay (gradient norm 0.133,
#          step 0.114): (a)'s `head_logp` holds the learner's.
#   exit_entropy 0.00072 | learning half of the batch 0.051, the tokens
#          and the actions swapped on the way to the learner 0.066, three
#          passes 0.16
#   loss 0.0064 | half of the batch 1.37, swapped 0.037, three passes
#          0.038
#   grad_norm 0.0585 (on a rollout the actions are the policy's own
#          samples and rho is 1: the gradient is the entropy and value
#          terms', and bfloat16 moves its norm by 2-6 % where the seeded
#          batch's moves by under 1 %) | half of the batch 0.45, swapped
#          0.75, three passes 1.26
#   step 0.0780: the chunk's parameters after its two optimizer steps
#          against the reference's, over the norm of the reference's
#          change (1.3e-4; float32's last bit is an eighth of a step of
#          1.5e-8 on a weight of 0.02) | half of the batch 0.81, no step
#          at all 1.0, swapped 1.32, `p - u` 2.0
# Loss, gradient norm and step are a training cell's: the precision
# hardly moves them, and their limits are about three times the reading;
# the others lie between their two readings.
CHUNK = {"logp_max_abs": 0.15, "logp_mean_abs": 0.03, "exit_entropy": 0.005,
         "loss": 0.019, "grad_norm": 0.18, "step": 0.22}


def reference_module():
    """`perfbench/references/ouro_looplm.py`, beside the harness (not
    under `--data-dir`: the reference is yardstick, not data)."""
    import childlib
    import discover

    return discover.module(os.path.dirname(os.path.abspath(childlib.__file__)),
                           "references", "ouro_looplm")


# -- operations per update, from shapes ----------------------------------------


def stack_matmul_params(section: dict) -> int:
    d, f = section["hidden_size"], section["intermediate_size"]
    a = section["num_attention_heads"] * section["head_dim"]
    return section["num_hidden_layers"] * (3 * d * a + a * d + 3 * d * f)


def forward_flops_per_token(section: dict) -> int:
    """One token through the learner's forward: R passes of the stack's
    matmuls (2 x 411.0 M x 4), attention's q k^T and p v over the mean
    causal length (R x L x 2 x 2 x T_k x H x d, T_k = (T + 1) / 2), and
    R head passes: the vocabulary matmul, the gate and the value."""
    r, t = section["total_ut_steps"], section["trajectory"]
    a = section["num_attention_heads"] * section["head_dim"]
    attention = r * section["num_hidden_layers"] * 2 * 2 * (t + 1) * a // 2
    heads = r * 2 * section["hidden_size"] * (section["vocab_size"] + 2)
    return r * 2 * stack_matmul_params(section) + attention + heads


def learn_flops_per_update(section: dict, torso=None,
                           batch: int | None = None) -> int:
    """Forward + backward (3 x forward) over `batch` episodes of
    `trajectory` tokens. NOT counted, as in the other cells: the acting
    pass (T decode steps at batch N, one forward's worth of operations
    again) and the rematerialised layers and head blocks. `learn_mfu`
    divides by ALL busy seconds, so it reads low by what those take.
    `torso` is what `flops.torso_macs` returns for a vector observation;
    a token has no torso and it is not read."""
    b = batch or section["envs_per_actor"] * section["num_actors"]
    return 3 * forward_flops_per_token(section) * b * section["trajectory"]


# -- the comparisons --------------------------------------------------------------


def hyper(agent) -> dict:
    cfg = agent.cfg
    return dict(num_heads=cfg.num_attention_heads, head_dim=cfg.head_dim,
                rope_theta=cfg.rope_theta, rms_eps=cfg.rms_norm_eps,
                loop_passes=cfg.total_ut_steps, discount=cfg.discount_factor,
                baseline_loss_coef=cfg.baseline_loss_coef,
                entropy_coef=cfg.entropy_coef,
                exit_entropy_coef=cfg.exit_entropy_coef,
                reward_clipping=cfg.reward_clipping,
                gradient_clip_norm=cfg.gradient_clip_norm,
                learning_rate=cfg.start_learning_rate,
                end_learning_rate=cfg.end_learning_rate,
                learning_frame=cfg.learning_frame)


def seeded_batch(section: dict, rows: int, seed: int) -> dict:
    """`[rows, T]` as numpy: tokens and actions over the whole
    vocabulary, behaviour log-probabilities around log(1 / V) (so rho is
    cut for about half of the steps), rewards on one step in four, the
    episode's end at the last step and one more inside row 0."""
    r = np.random.RandomState(seed % (2 ** 32))
    t, v = section["trajectory"], section["vocab_size"]
    done = np.zeros((rows, t), bool)
    done[:, -1] = True
    done[0, t // 3] = True
    return {"tokens": r.randint(0, v, (rows, t)).astype(np.int32),
            "action": r.randint(0, v, (rows, t)).astype(np.int32),
            "behaviour_logp": (np.log(1.0 / v) + 0.3 * r.normal(size=(rows, t))
                               ).astype(np.float32),
            "reward": r.choice([0.0, 0.0, 0.0, 1.0], size=(rows, t)
                               ).astype(np.float32),
            "done": done}


def perturbed(params, seed: int):
    """The parameters with norm scales and head biases moved off their
    initial 1 and 0 (which every precision represents exactly)."""
    import jax

    p = dict(params["params"])
    for i, name in enumerate(("norms", "final_norm", "b_exit", "b_value")):
        key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)), i)
        p[name] = p[name] + 0.1 * jax.random.normal(key, p[name].shape,
                                                    p[name].dtype)
    return {"params": p}


def program_outputs(agent, params, opt_state, nb: dict, precision=None) -> dict:
    """The program's own forward of every pass, loss terms, gradient
    norm and the norm of the parameters' change in one step of its
    optimizer, through `agent._loss`, `agent.tx` and the model's
    methods; only these come back from the one jitted call."""
    import jax
    import jax.numpy as jnp

    from distributed_reinforcement_learning_tpu.agents import common
    from distributed_reinforcement_learning_tpu.agents.looplm import LoopLMBatch

    model = agent.model

    def run(p, opt, b):
        grads, metrics = jax.grad(agent._loss, has_aux=True)(p, b)
        updates, _ = agent.tx.update(grads, opt, p)
        # The new parameters as an array of the parameters' own dtype
        # would hold them: without the barrier the TPU compiler folds the
        # round trip through that dtype away and `moved` is `u` again
        # whatever the parameters' precision (seen on the chip, PR 30).
        new = jax.lax.optimization_barrier(jax.tree.map(
            lambda x, u: (x + u).astype(x.dtype), p, updates))
        moved = jax.tree.map(lambda y, x: y - x, new, p)
        hs = model.apply(p, b.tokens, b.done, method=model.trunk)
        logits, gate, value = model.apply(p, hs, method=model.logits)
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1),
            b.action[None, ..., None], axis=-1)[..., 0]
        return {"logits": logits, "gate": gate, "value": value, "logp": logp,
                "stats_logp": agent._stats(p, b)["logp"],
                "grad_norm": common.global_norm(grads),
                "update_norm": common.global_norm(moved),
                **{k: metrics[k] for k in LOSS_TERMS}}

    jitted = jax.jit(run)
    batch = LoopLMBatch(**nb)
    if precision is None:
        return jax.device_get(jitted(params, opt_state, batch))
    with jax.default_matmul_precision(precision):
        return jax.device_get(jitted(params, opt_state, batch))


LOSS_TERMS = ("total_loss", "pi_loss", "baseline_loss", "entropy")


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(1e-12, np.max(np.abs(want))))


def _nats(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _loss_distance(got: dict, want: dict) -> float:
    """The loss terms' largest distance, relative to the summed
    magnitude of the policy-gradient terms, which cancel."""
    scale = max(1.0, float(want["pi_scale"]))
    return max(abs(float(got[k]) - float(want[k])) / scale for k in LOSS_TERMS)


def distances(got: dict, want: dict, action=None) -> dict:
    """Each quantity's largest distance: relative to the reference's
    largest magnitude; `logp` in nats. With `action` (and the program's
    `stats_logp`), `head_logp`: the learner's blocked head
    (`token_stats`) against the REFERENCE's float32 log-softmax
    (`logp_of`) of the logits the program's plain head gave, so that the
    trunk's rounding, which `logp` carries, cancels."""
    # Pass by pass from the LAST, which is the one that acts: a program
    # with another number of passes is held against the reference's last.
    passes = min(len(got["logits"]), len(want["logits"]))
    last = lambda x: np.asarray(x)[-passes:]
    out = {k: _rel(last(got[k]), last(want[k]))
           for k in ("logits", "gate", "value")}
    out.update({k: _rel(got[k], want[k]) for k in ("grad_norm", "update_norm")})
    out["logp"] = _nats(last(got["logp"]), last(want["logp"]))
    if action is not None:
        out["head_logp"] = _nats(got["stats_logp"], reference_module().logp_of(
            got["logits"], np.broadcast_to(action, got["stats_logp"].shape)))
    out["loss"] = _loss_distance(got, want)
    return out


def within(dist: dict, limits: dict) -> bool:
    return all(k in dist and np.isfinite(dist[k]) and dist[k] <= limits[k]
               for k in limits)


def highest_twin(agent):
    """The same agent computing in float32 with dense attention: run
    under `jax.default_matmul_precision("highest")`."""
    import dataclasses

    import jax.numpy as jnp

    return type(agent)(dataclasses.replace(
        agent.cfg, dtype=jnp.float32, attention_backend="reference"))


def reference_check(agent, train_state, section: dict, seed: int,
                    hp: dict | None = None) -> dict:
    """Comparison (a) of the module's docstring. `hp` is the
    configuration's (what `agent` was built from, unless a test plants a
    fault in `agent`)."""
    ref = reference_module()
    params = perturbed(train_state.params, seed)
    nb = seeded_batch(section, REFERENCE_ROWS, seed)
    want = ref.evaluate(params, nb, hp or hyper(agent))
    out = {"ok": True, "limits": {"stated": STATED, "highest": HIGHEST},
           "distance": {}, "reference": {
               "loss": float(want["total_loss"]),
               "grad_norm": float(want["grad_norm"]),
               "update_norm": float(want["update_norm"])}}
    for name, prog, precision, limits in (
            ("stated", agent, None, STATED),
            ("highest", highest_twin(agent), "highest", HIGHEST)):
        got = program_outputs(prog, params, train_state.opt_state, nb, precision)
        dist = distances(got, want, nb["action"])
        out["distance"][name] = dist
        out["ok"] = out["ok"] and within(dist, limits)
    return out


def param_sample(params) -> list:
    """A strided sample of every leaf (all of a small one), on the host:
    at most `SAMPLE` elements a leaf, the same places for the same
    shapes."""
    import jax

    def take(x):
        flat = x.reshape(-1)
        return flat[::max(1, flat.shape[0] // SAMPLE)]

    return [np.asarray(a) for a in jax.device_get(
        jax.jit(lambda p: [take(x) for x in jax.tree.leaves(p)])(params))]


def chunk_record(before: list, after: list, metrics: dict) -> dict:
    """What comparison (b) replays, as flat numpy arrays (an `.npz`):
    `param_sample` of the parameters a chunk started from and ended
    with, and that chunk's own stacked metrics: the `[U, N, T]` rollout
    of every update and what each update logged."""
    out = {f"before_{i}": a for i, a in enumerate(before)}
    out.update({f"after_{i}": a for i, a in enumerate(after)})
    out.update({f"rollout_{k}": np.asarray(v)
                for k, v in metrics["rollout"].items()})
    out.update({f"logged_{k}": np.asarray(metrics[k])
                for k in (*LOSS_TERMS, "grad_norm", "exit_entropy")})
    return out


def _add_into(acc: list, grads: list) -> None:
    """`acc[i] += grads[i]`, a leaf at a time, dropping each addend as it
    is used: at most one leaf more than the two lists is alive."""
    for i in range(len(acc)):
        acc[i] = acc[i] + grads[i]
        grads[i] = None


def reference_update(ref, theirs, nu, rollout: dict, hp: dict, step: int):
    """One update of the fused loop as the plain reference computes it
    from the update's own `[N, T]` rollout: the loss terms, log pi^(R)
    of the taken actions and the gradients, summed over blocks of
    `CHUNK_BLOCK` rows, then one optimizer step -> (terms, theirs, nu)."""
    import jax

    rows = rollout["tokens"].shape[0]
    sums = dict.fromkeys((*LOSS_TERMS, "exit_entropy", "pi_scale"), 0.0)
    logp, acc, tree = [], None, None
    for i in range(0, rows, CHUNK_BLOCK):
        terms, grads = ref.loss_and_grads(
            theirs, {k: v[i:i + CHUNK_BLOCK] for k, v in rollout.items()}, hp)
        for k in sums:
            sums[k] += float(terms[k])
        logp.append(np.asarray(terms["logp"][-1]))
        del terms
        leaves, tree = jax.tree.flatten(grads)
        del grads
        if acc is None:
            acc = leaves
        else:
            _add_into(acc, leaves)
    grads = jax.tree.unflatten(tree, acc)
    del acc
    sums["grad_norm"] = float(ref.clip_scale(grads, hp)[0])
    sums["logp"] = np.concatenate(logp)
    steps = rows * (rollout["tokens"].shape[1] - 2)  # the loss's first view
    sums["exit_entropy"] /= steps  # the program logs its mean
    theirs, nu = ref.rmsprop_step(theirs, nu, grads, hp, step)
    return sums, theirs, nu


def chunk_check(agent, params, record: dict) -> dict:
    """Comparison (b) of the module's docstring, under `agent`'s
    configuration. `params`: the parameters the recorded chunk started
    from, made anew from the seed; CONSUMED (their device buffers are
    freed once the reference has its own copy: 2.45 GB that the replay's
    gradients need)."""
    import jax

    ref = reference_module()
    hp = hyper(agent)
    leaves = jax.tree.leaves(params)
    before = [record[f"before_{i}"] for i in range(len(leaves))]
    if not all(np.array_equal(a, b)
               for a, b in zip(param_sample(params), before)):
        return {"ok": False, "why": "the parameters made anew from the seed "
                "are not those the recorded chunk started from"}
    theirs = ref.rekey(params)
    kept = {id(x) for x in jax.tree.leaves(theirs)}
    for x in leaves:
        if id(x) not in kept:
            x.delete()
    rollouts = {k[len("rollout_"):]: v for k, v in record.items()
                if k.startswith("rollout_")}
    updates = rollouts["tokens"].shape[0]
    dist = dict.fromkeys(CHUNK, 0.0)
    nu, told = None, []
    for u in range(updates):
        rollout = {k: v[u] for k, v in rollouts.items()}
        want, theirs, nu = reference_update(ref, theirs, nu, rollout, hp, u)
        if u + 1 < updates:  # 2.45 GB that the next update's gradients need
            nu = jax.device_get(nu)
        diff = np.abs(rollout["behaviour_logp"].astype(np.float64)
                      - want["logp"])
        got = {k: record[f"logged_{k}"][u] for k in
               (*LOSS_TERMS, "grad_norm", "exit_entropy")}
        here = {"loss": _loss_distance(got, want),
                "exit_entropy": _rel(got["exit_entropy"], want["exit_entropy"]),
                "grad_norm": _rel(got["grad_norm"], want["grad_norm"]),
                "logp_max_abs": float(diff.max()),
                "logp_mean_abs": float(diff.mean())}
        dist.update({k: max(dist[k], v) for k, v in here.items()})
        told.append({"loss": want["total_loss"], "grad_norm": want["grad_norm"],
                     "logp_mean": float(want["logp"].mean())})
    # The parameters' change over the chunk, on the same strided sample
    # of every leaf, all leaves as one vector.
    flat = lambda sample: np.concatenate(
        [np.asarray(a, np.float64).reshape(-1) for a in sample])
    after = flat([record[f"after_{i}"] for i in range(len(leaves))])
    theirs_after = flat(param_sample(ref.stacked(theirs)))
    moved = theirs_after - flat(before)
    dist["step"] = float(np.linalg.norm(after - theirs_after)
                         / max(1e-30, np.linalg.norm(moved)))
    return {"ok": within(dist, CHUNK), "distance": dist, "limits": CHUNK,
            "updates": updates, "steps": int(diff.size) * updates,
            "reference": told, "reference_moved": float(np.linalg.norm(moved))}
