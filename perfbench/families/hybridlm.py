"""Family `hybridlm`: a hybrid state-space / attention language model
(configuration `granite_hybrid`: granite-4.0-h-micro's nine Mamba-2
layers to one grouped-query attention layer, 2048 wide, a 12,544-row
slice of its tied vocabulary) as the policy of the token-level IMPALA in
the fused loop `runtime/anakin_tokens.py`: what the mode
`anakin_tokens_hybrid` and `reducers/learn_mfu.py` ask of a family:
operations per update from shapes, and the two comparisons with the
plain reference `references/granite_hybrid.py` that decide `correct`.
The arithmetic of the distances is `families/looplm.py`'s, loaded from
there; the limits, the batch and the replay are this file's.

(a) `reference_check`, on a seeded batch of 2 x 1,024 tokens with
    non-trivial actions, rewards, behaviour log-probabilities and an
    episode end inside a row (so inside a chunk of the scan): logits,
    values, taken-action log-probabilities, the loss terms, the
    gradients' norm and the norm of the parameters' change in one
    optimizer step, of the program (bfloat16 operands, as the
    configuration states; the CHUNKED scan) and of a `highest` twin of it
    (float32 operands, `highest` matmuls, dense attention), against the
    float32 `highest` reference (the step-by-step recurrence).
(b) `chunk_check`, of what the COMPILED CHUNK THAT THE WINDOW DRIVES
    itself produced, at the timed sizes (32 x 1,024): the reference
    replays the first warm chunk from the parameters it started from, on
    the update's own rollout. Held against it: the log mu(a_t) that
    collect wrote through the three kinds of act-time state (all 32,768
    steps), the recurrent state the episode ended with (a strided
    sample), the loss terms, the gradient norm, `dt_mean` and
    `decay_min` that the update logged, and the parameters the chunk
    ended with (a strided sample of every leaf) against the reference's
    own RMSProp step.

The reference works a ROW at a time, every layer rematerialised (one
row's float32 intermediates of ten layers are 6 GB at these widths, of
one layer 0.6), and its gradients add up leaf by leaf: the reference's
parameters, the sum and one row's gradients are 9.3 GB of the chip.

LIMITS. Every distance is relative to the reference's largest magnitude
of that quantity, except log-probabilities, which are held in nats. Each
limit lies between two readings (my chip runs, PR 32; PERF.md section
6): the largest the program gave over its seeds, and what a program in
the nearest precision below gives. `perfbench/tests/
test_granite_hybrid_faults.py` plants each wrong program at a small size
and holds that `ok` comes out false.
"""

from __future__ import annotations

import functools
import os

import numpy as np

REFERENCE_ROWS = 2
STATE_SAMPLE = 16384  # `agents/hybridlm.py`'s: elements of the final state logged

# (a), the program as timed against the `highest` reference. The distance
# is the rounding to bfloat16 of every matmul operand and of the residual
# stream between 10 layers. Readings: the largest over the seeds of my
# chip runs, PR 32 (PERF.md section 6 has every one) | what reads over
# the limit (a scratch script on the chip, one seed, PR 32):
#   logits 0.0083 (0.0072 to 0.0083 over ten seeds) | a chunk boundary that drops H_{c-1} 0.076; dt without
#          its bias 0.49; the gate after the norm 0.48; a decay of the
#          wrong sign NaN
#   value  0.0242 (0.0175 to 0.0242) | dropped H_{c-1} 0.107
#   logp   0.0088 nats | dropped H_{c-1} 0.059; the reference in bfloat16
#          0.067 (its log-softmax is bfloat16 too)
#   head_logp 9.5e-7 nats (the learner's blocked log-softmax against the
#          REFERENCE's float32 one of the logits the program's plain head
#          gave: the trunk's rounding cancels) | a bfloat16 log-softmax
#          0.063
#   update_norm 9.2e-6 (the clip at 40 is always on, so the step's norm is
#          lr x 40 / sqrt(1.09) whatever the gradient's direction) |
#          bfloat16 PARAMETERS 0.855, the reference in bfloat16 0.855: a
#          step of 1.4e-8 a weight is under their last bit
#   loss 0.00085, grad_norm 0.00084: the precision hardly moves them (the
#          bfloat16 reference reads 0.0084 / 0.0064), so they take the
#          limits of `families/looplm.py`, 26 and 70 times the reading.
# The reference in the nearest precision below (bfloat16 parameters,
# activations, recurrent state, softmax and loss) reads logits 0.0147,
# value 0.0267: the SAME as the program, whose residual stream is bfloat16
# too; it is refused by `update_norm` and by `logp`. A bfloat16 state
# ACROSS CHUNKS reads inside every one of these (logits 0.0077): it is the
# twin's to refuse, below.
STATED = {"logits": 0.025, "value": 0.06, "logp": 0.025, "head_logp": 1e-4,
          "loss": 0.022, "grad_norm": 0.06, "update_norm": 1e-4}
# (a), the `highest` twin against the same reference: the CHUNKED scan
# against the step-by-step recurrence, the same arithmetic in another
# order; what is left is float32 rounding. Largest over the seeds: logits
# 1.14e-5, value 2.08e-5, logp 8.6e-6 nats, head_logp 6.7e-6, loss 1.5e-6,
# grad_norm 1.8e-6, update_norm 4.1e-7. A bfloat16 state across chunks
# reads logits 8.4e-5, value 1.36e-4, logp 5.1e-5: the limits of those
# three lie between the two readings (2.3 to 3.5 times from each); every
# other wrong program above reads three orders over.
HIGHEST = {"logits": 3e-5, "value": 6e-5, "logp": 3e-5, "head_logp": 1e-4,
           "loss": 5e-5, "grad_norm": 2e-5, "update_norm": 1e-5}
# (b), the compiled chunk against the reference's replay of it (one
# update a chunk). Largest over the runs of the cell (my chip runs, PR 32)
# | what reads over the limit:
#   logp_max_abs 0.0094 nats, logp_mean_abs 0.00159 (bfloat16 again: the
#          decode step rounds where the reference's forward does not) | at
#          a small size on the CPU (the faults test): a state not reset, a
#          window shifted by one, key/value heads not grouped
#   state 0.0249 (0.0163 to 0.0249 over fourteen runs): the recurrent state the
#          episode ended with, a strided sample of 16,384, its distance
#          from the reference's in the 2-norm over the reference's norm
#          (the largest element's distance over the largest magnitude,
#          `state_max`, is told and not held: it reads 0.0075 to 0.0411) |
#          a decay of the wrong sign, a state not reset (the faults test).
#          A bfloat16 act-time state reads 0.0198 (`state_max` 0.0238): by
#          its accuracy it CANNOT be told from the float32 state behind
#          bfloat16 matmul operands, so the MODE holds the state to its
#          bytes (`state_problems`) and this limit, 2.4 times the
#          largest reading, holds its arithmetic
#   dt_mean 1.4e-5 | dt without its bias (the faults test)
#   loss 0.0023, grad_norm 0.0019 | learning half of the batch (the
#          faults test): `families/looplm.py`'s limits, 8 and 95 times
#          the reading
#   step 0.0122: the chunk's parameters after its optimizer step against
#          the reference's, over the norm of the reference's change
#          (2.1e-4) | no step at all 1.0, `p - u` 2.0: between the reading
#          and 1, with ten times of room on either side.
CHUNK = {"logp_max_abs": 0.03, "logp_mean_abs": 0.005, "state": 0.06,
         "dt_mean": 1e-3, "loss": 0.019, "grad_norm": 0.18, "step": 0.1}
LOSS_TERMS = ("total_loss", "pi_loss", "baseline_loss", "entropy")
LOGGED = (*LOSS_TERMS, "grad_norm", "dt_mean", "decay_min", "state_sample")


def _harness_dir() -> str:
    import childlib

    return os.path.dirname(os.path.abspath(childlib.__file__))


@functools.lru_cache(maxsize=None)
def reference_module():
    """`perfbench/references/granite_hybrid.py`, beside the harness (not
    under `--data-dir`: the reference is yardstick, not data). Loaded
    once: its jitted pieces then compile once for both comparisons."""
    import discover

    return discover.module(_harness_dir(), "references", "granite_hybrid")


@functools.lru_cache(maxsize=None)
def looplm():
    """`families/looplm.py`: the distances' arithmetic, shared."""
    import discover

    return discover.module(_harness_dir(), "families", "looplm")


# -- operations per update, from shapes ----------------------------------------


def forward_flops_per_token(section: dict) -> int:
    """One token through the learner's forward: every layer's matmuls,
    the chunked scan's four einsums as it computes them (a dense `[Q, Q]`
    block a chunk: C.B^T, the decay-weighted product with x, the chunk's
    state, the read of the carried state), attention's q k^T and p v over
    the mean causal length, and the tied vocabulary head with the value."""
    d, f, t = (section["hidden_size"], section["shared_intermediate_size"],
               section["trajectory"])
    h, p, n = (section["mamba_n_heads"], section["mamba_d_head"],
               section["mamba_d_state"])
    q = min(section["mamba_chunk_size"], t)
    heads = section["num_attention_heads"]
    hd = d // heads
    mlp = 2 * (d * 2 * f + f * d)
    mamba = (2 * (d * (2 * h * p + 2 * n + h) + h * p * d)
             + 2 * q * n + 2 * h * q * p + 2 * 2 * h * p * n)
    attention = (2 * (d * heads * hd + 2 * d * section["num_key_value_heads"] * hd
                      + heads * hd * d) + 2 * 2 * (t + 1) * heads * hd // 2)
    kinds = section["layer_types"]
    return (kinds.count("mamba") * (mamba + mlp)
            + kinds.count("attention") * (attention + mlp)
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
                attention_multiplier=cfg.attention_multiplier,
                residual_multiplier=cfg.residual_multiplier,
                embedding_multiplier=cfg.embedding_multiplier,
                logits_scaling=cfg.logits_scaling,
                mamba_n_heads=cfg.mamba_n_heads, mamba_d_head=cfg.mamba_d_head,
                mamba_d_state=cfg.mamba_d_state, rms_eps=cfg.rms_norm_eps,
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
    an episode's end inside row 0: here inside a chunk of the scan)."""
    return looplm().seeded_batch(section, rows, seed)


def perturbed(params, seed: int):
    """The parameters with norm scales, biases and the skip moved off
    their initial 1 and 0 (which every precision represents exactly)."""
    import jax

    key = jax.random.PRNGKey(seed % (2 ** 31))
    moved = ("norms", "final_norm", "b_value", "conv_b", "gate_norm", "D")
    count = [0]

    def move(path, x):
        if path[-1].key not in moved:
            return x
        count[0] += 1
        return x + 0.1 * jax.random.normal(jax.random.fold_in(key, count[0]),
                                           x.shape, x.dtype)

    return jax.tree_util.tree_map_with_path(move, params)


def reference_sums(ref, theirs, batch: dict, hp: dict, precision="highest",
                   logits: bool = True):
    """The reference's loss terms, per-step outputs and gradients of
    `batch`, a row at a time -> (terms: sums over rows, `value`, `logp`,
    `states` with every row, `logits` only if asked (1.6 GB at 32 rows);
    gradients as float32 leaves summed over the rows, leaf by leaf)."""
    import jax

    lm = looplm()
    rows = batch["tokens"].shape[0]
    sums = dict.fromkeys((*LOSS_TERMS, "pi_scale"), 0.0)
    per_row = {k: [] for k in ("logits", "value", "logp", "states")}
    dt_means, decay_mins = [], []
    acc = None
    for i in range(rows):
        terms, grads = ref.loss_and_grads(
            theirs, {k: v[i:i + 1] for k, v in batch.items()}, hp, precision)
        for k in sums:
            sums[k] += float(terms[k])
        dt_means.append(float(terms["dt_mean"]))
        decay_mins.append(float(terms["decay_min"]))
        for k in ("logits", "value", "logp") if logits else ("value", "logp"):
            per_row[k].append(np.asarray(terms[k], np.float32))
        per_row["states"].append([np.asarray(s, np.float32)
                                  for s in terms["states"]])
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
    out["dt_mean"] = float(np.mean(dt_means))
    out["decay_min"] = float(np.min(decay_mins))
    out["grad_norm"] = float(np.sqrt(sum(
        float(jax.numpy.sum(jax.numpy.square(g))) for g in acc)))
    return out, acc


def reference_step(ref, theirs, nu, grads: list, hp: dict, step: int,
                   grad_norm: float, keep: bool = True):
    """One RMSProp step of the configuration, leaf by leaf (each gradient
    leaf is dropped as it is used) -> (theirs, nu, the norm of the
    parameters' change, and for every leaf, in `theirs`' layout, the
    largest step in units of float32's spacing at the parameter it moves
    (`step_over_last_bit`, shape `[1]`)). Without `keep` only the norm
    comes back: the new parameters and moments are dropped leaf by leaf."""
    import jax
    import jax.numpy as jnp

    scale = min(1.0, hp["gradient_clip_norm"] / max(grad_norm, 1e-30))
    lr = ref.learning_rate(step, hp)
    leaves, tree = jax.tree.flatten(theirs)
    nus = nu if nu is not None else [1.0] * len(leaves)
    new, new_nu, bits, moved = [], [], [], 0.0
    for i, (p, n) in enumerate(zip(leaves, nus)):
        g, grads[i] = grads[i] * scale, None
        bits.append(ref.step_over_last_bit(p, n, g, lr).reshape(1))
        q, n = ref.rmsprop_leaf(p, n, g, lr)
        moved += float(jnp.sum(jnp.square((q - p).astype(jnp.float32))))
        if keep:
            new.append(q)
            new_nu.append(n)
    if not keep:
        return None, None, moved ** 0.5, None
    return (jax.tree.unflatten(tree, new), new_nu, moved ** 0.5,
            jax.tree.unflatten(tree, bits))


def program_outputs(agent, params, nb: dict, precision=None) -> dict:
    """The program's own forward, loss terms, gradient norm and the norm
    of the parameters' change in one step of its optimizer (second
    moments as `tx.init` makes them, inside the jit: no array of ones),
    through `agent._loss`, `agent.tx` and the model's methods. Two jitted
    calls, the gradients donated to the second: the step's new parameters
    then take the gradients' place and not 3.1 GB of their own."""
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
        return grads, {"logits": logits, "value": value, "logp": logp,
                       "stats_logp": agent._stats(p, b)["logp"],
                       "grad_norm": common.global_norm(grads),
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


def distances(got: dict, want: dict, action=None) -> dict:
    """Each quantity's largest distance: relative to the reference's
    largest magnitude; `logp` in nats; `head_logp`: the learner's blocked
    head against the REFERENCE's float32 log-softmax of the logits the
    program's plain head gave (the trunk's rounding cancels)."""
    lm = looplm()
    out = {k: lm._rel(got[k], want[k])
           for k in ("logits", "value", "grad_norm", "update_norm")}
    out["logp"] = lm._nats(got["logp"], want["logp"])
    if action is not None:
        out["head_logp"] = lm._nats(got["stats_logp"], reference_module().logp_of(
            got["logits"], np.broadcast_to(action, got["stats_logp"].shape)))
    out["loss"] = lm._loss_distance(got, want)
    return out


def within(dist: dict, limits: dict) -> bool:
    return looplm().within(dist, limits)


def highest_twin(agent):
    """The same agent computing in float32 with dense attention: run
    under `jax.default_matmul_precision("highest")`."""
    return looplm().highest_twin(agent)


def consume(params, theirs) -> None:
    """Free the device buffers of `params` that `theirs` does not share:
    3.1 GB that the reference's gradients need."""
    import jax

    kept = {id(x) for x in jax.tree.leaves(theirs)}
    for x in jax.tree.leaves(params):
        if id(x) not in kept:
            x.delete()


def reference_check(agent, train_state, section: dict, seed: int,
                    hp: dict | None = None) -> dict:
    """Comparison (a) of the module's docstring. `hp` is the
    configuration's (what `agent` was built from, unless a test plants a
    fault in `agent`). `train_state` is CONSUMED: the program's two sides
    run first, then its parameters make room for the reference's."""
    import jax

    ref = reference_module()
    hp = hp or hyper(agent)
    params = perturbed(train_state.params, seed)
    # Its second moments are 3.1 GB that nothing here reads, and the
    # caller's frame holds the state for as long as this call lasts.
    for x in jax.tree.leaves(train_state.opt_state):
        getattr(x, "delete", lambda: None)()
    del train_state
    nb = seeded_batch(section, REFERENCE_ROWS, seed)
    got = {}
    for name, prog, precision in (("stated", agent, None),
                                  ("highest", highest_twin(agent), "highest")):
        got[name] = program_outputs(prog, params, nb, precision)
        jax.clear_caches()  # the executable's scratch, before the next one
    theirs = ref.rekey(params, hp["layer_order"])
    consume(params, theirs)
    del params
    want, grads = reference_sums(ref, theirs, nb, hp)
    _, _, want["update_norm"], _ = reference_step(
        ref, theirs, None, grads, hp, 0, want["grad_norm"], keep=False)
    del theirs, grads
    out = {"ok": True, "limits": {"stated": STATED, "highest": HIGHEST},
           "distance": {}, "reference": {
               "loss": float(want["total_loss"]),
               "grad_norm": float(want["grad_norm"]),
               "update_norm": float(want["update_norm"])}}
    for name, limits in (("stated", STATED), ("highest", HIGHEST)):
        dist = distances(got[name], want, nb["action"])
        out["distance"][name] = dist
        out["ok"] = out["ok"] and within(dist, limits)
    return out


def param_sample(params) -> list:
    return looplm().param_sample(params)


def chunk_record(before: list, after: list, metrics: dict) -> dict:
    """What comparison (b) replays, as flat numpy arrays (an `.npz`):
    `param_sample` of the parameters a chunk started from and ended
    with, and that chunk's own stacked metrics: the `[U, N, T]` rollout
    of every update and what each update logged."""
    out = {f"before_{i}": a for i, a in enumerate(before)}
    out.update({f"after_{i}": a for i, a in enumerate(after)})
    out.update({f"rollout_{k}": np.asarray(v)
                for k, v in metrics["rollout"].items()})
    out.update({f"logged_{k}": np.asarray(metrics[k]) for k in LOGGED})
    return out


def state_sample(states: list) -> np.ndarray:
    """`agents/hybridlm.py` `state_counters`' strided sample, of the
    reference's final states (`[N, H, P, S]` a state-space layer)."""
    every = max(1, sum(s.size for s in states) // STATE_SAMPLE)
    return np.concatenate([s.reshape(-1)[::every] for s in states])


def chunk_check(agent, params, record: dict) -> dict:
    """Comparison (b) of the module's docstring, under `agent`'s
    configuration. `params`: the parameters the recorded chunk started
    from, made anew from the seed; CONSUMED (their device buffers are
    freed once the reference has its own copy)."""
    import jax

    lm = looplm()
    ref = reference_module()
    hp = hyper(agent)
    leaves = jax.tree.leaves(params)
    before = [record[f"before_{i}"] for i in range(len(leaves))]
    if not all(np.array_equal(a, b)
               for a, b in zip(param_sample(params), before)):
        return {"ok": False, "why": "the parameters made anew from the seed "
                "are not those the recorded chunk started from"}
    theirs = ref.rekey(params, hp["layer_order"])
    consume(params, theirs)
    rollouts = {k[len("rollout_"):]: v for k, v in record.items()
                if k.startswith("rollout_")}
    updates = rollouts["tokens"].shape[0]
    dist = dict.fromkeys((*CHUNK, "state_max"), 0.0)  # `state_max`: told, not held
    nu, told = None, []
    for u in range(updates):
        rollout = {k: v[u] for k, v in rollouts.items()}
        want, grads = reference_sums(ref, theirs, rollout, hp, logits=False)
        theirs, nu, _, bits = reference_step(ref, theirs, nu, grads, hp, u,
                                             want["grad_norm"])
        del grads
        # the moments wait on the host (3.1 GB that the next update's
        # gradients need); after the last update nothing reads them
        nu = jax.device_get(nu) if u + 1 < updates else None
        if u == 0:  # in the program's layout and order of leaves
            last_bit = [float(np.max(x)) for x in
                        jax.tree.leaves(ref.stacked(bits))]
        diff = np.abs(rollout["behaviour_logp"].astype(np.float64)
                      - want["logp"][0])
        got = {k: record[f"logged_{k}"][u] for k in LOGGED}
        theirs_state = state_sample(want["states"])
        here = {"loss": lm._loss_distance(got, want),
                "grad_norm": lm._rel(got["grad_norm"], want["grad_norm"]),
                "dt_mean": lm._rel(got["dt_mean"], want["dt_mean"]),
                "state": float(np.linalg.norm(got["state_sample"] - theirs_state)
                               / np.linalg.norm(theirs_state)),
                "state_max": lm._rel(got["state_sample"], theirs_state),
                "logp_max_abs": float(diff.max()),
                "logp_mean_abs": float(diff.mean())}
        dist.update({k: max(dist[k], v) for k, v in here.items()})
        told.append({"loss": want["total_loss"], "grad_norm": want["grad_norm"],
                     "logp_mean": float(want["logp"].mean())})
    flat = lambda sample: np.concatenate(
        [np.asarray(a, np.float64).reshape(-1) for a in sample])
    after = flat([record[f"after_{i}"] for i in range(len(leaves))])
    theirs_after = flat(param_sample(ref.stacked(theirs)))
    moved = theirs_after - flat(before)
    dist["step"] = float(np.linalg.norm(after - theirs_after)
                         / max(1e-30, np.linalg.norm(moved)))
    return {"ok": within(dist, CHUNK), "distance": dist, "limits": CHUNK,
            "updates": updates, "steps": int(diff.size) * updates,
            "reference": told, "reference_moved": float(np.linalg.norm(moved)),
            # leaf by leaf, the reference's own first step over float32's
            # spacing at the parameter: a leaf under 1 everywhere cannot be
            # told from one that stays (the mode reads this)
            "step_over_last_bit": last_bit}
