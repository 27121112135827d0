"""R2D2-Atari as published, written plainly: the network, the loss and
the prioritized sampler that `agents/r2d2.py`, `models/r2d2_net.py` and
`data/device_replay.py` are held against (section `r2d2_atari`).

Source: Kapturowski et al., *Recurrent Experience Replay in Distributed
Reinforcement Learning* (ICLR 2019), section 2.3 and the appendix's
hyper-parameter table. Straightforward `jax.numpy` in float32: no flax
module, no kernel, no batching trick beyond a leading batch dimension;
the recurrence is a bare `lax.scan` over time and the n-step target two
Python loops that spell the sum out. The sampler and the ring are plain
numpy. Nothing here imports the program.

`perfbench/references/r2d2_atari.py` is the benchmark's own copy of this
file (a test keeps the two the same text): the yardstick does not move
when the program's package does.

Departures from the paper, each also a comment where it happens:
- the 1/255 of the frame normalization is folded into the first
  convolution's kernel, as the program folds it, so that both round the
  same operands where a matmul rounds them;
- the previous action enters through the program's embedding (one-hot ->
  256 -> 256, relu) beside the convolution's features; the paper feeds
  the one-hot action and the reward to the LSTM directly;
- the LSTM adds 1 to the forget gate (TF1's `LSTMCell`);
- (h, c) are zeroed AFTER the step at which `done` is set, so a stored
  sequence may run across an episode's end;
- where t + n runs past the sequence the n-step horizon is cut at its
  last step (as `rlax.n_step_bootstrapped_returns` does); the paper's
  actors hold the n following steps;
- priority exponent 0.6 and an importance exponent that the caller
  anneals (paper: 0.9 and a fixed 0.6).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

CONV_STRIDES = (4, 2, 1)  # Nature-DQN: 8x8/4 x32, 4x4/2 x64, 3x3/1 x64, VALID
PER_EPS = 0.001
PER_ALPHA = 0.6  # paper: 0.9


def rekey(program_params) -> dict:
    """The program's parameter tree (flax, `R2D2Net` with
    `dueling_hidden`) under this file's flat names. Explicit on purpose:
    a renamed or missing parameter fails here, by name."""
    p = program_params["params"]
    dense = lambda d: (d["kernel"], d["bias"])
    return {
        "conv": [(p["torso"][f"conv{i}_kernel"], p["torso"][f"conv{i}_bias"])
                 for i in range(3)],
        "embed": [dense(p["action_embed"]["Dense_0"]),
                  dense(p["action_embed"]["Dense_1"])],
        "lstm": (p["cell"]["gates_kernel"], p["cell"]["gates_bias"]),
        "value": [dense(p["value_fc"]), dense(p["value_out"])],
        "advantage": [dense(p["advantage_fc"]), dense(p["advantage_out"])],
    }


def features(p: dict, obs, prev_action, dtype=jnp.float32):
    """`obs [N, 84, 84, 4]` uint8, `prev_action [N]` -> `[N, 3136 + 256]`."""
    x = obs.astype(dtype)
    for i, ((kernel, bias), stride) in enumerate(zip(p["conv"], CONV_STRIDES)):
        kernel = kernel.astype(dtype)
        if i == 0:  # departure: frame / 255 folded into the kernel
            kernel = kernel * jnp.asarray(1.0 / 255.0, dtype)
        x = jax.lax.conv_general_dilated(
            x, kernel, (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + bias.astype(dtype))
    x = x.reshape((x.shape[0], -1))
    num_actions = p["embed"][0][0].shape[0]
    a = jax.nn.one_hot(prev_action, num_actions, dtype=dtype)
    for kernel, bias in p["embed"]:  # departure: the program's embedding
        a = jax.nn.relu(a @ kernel.astype(dtype) + bias.astype(dtype))
    return jnp.concatenate([x, a], axis=-1)


def lstm_step(p: dict, z, h, c):
    """One step on `z [B, F]`: gates from `[z; h] @ W + b`."""
    kernel, bias = p["lstm"]
    gates = jnp.concatenate([z, h], axis=-1) @ kernel.astype(z.dtype) \
        + bias.astype(z.dtype)
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    return jax.nn.sigmoid(o) * jnp.tanh(c), c


def dueling_head(p: dict, h):
    """Q = V + A - mean_a A, each stream with one hidden layer."""
    stream = lambda layers: (
        jax.nn.relu(h @ layers[0][0].astype(h.dtype) + layers[0][1].astype(h.dtype))
        @ layers[1][0].astype(h.dtype) + layers[1][1].astype(h.dtype))
    value, advantage = stream(p["value"]), stream(p["advantage"])
    q = value + advantage - jnp.mean(advantage, axis=-1, keepdims=True)
    return q.astype(jnp.float32)


def q_values(p: dict, batch: dict, dtype=jnp.float32):
    """Q over a stored sequence from its stored start state: `batch`
    holds `state [B,T,84,84,4]` uint8, `previous_action [B,T]`, `done
    [B,T]`, `initial_h`, `initial_c [B,H]` -> `[B, T, A]` float32."""
    b, t = batch["previous_action"].shape
    z = features(p, batch["state"].reshape((b * t,) + batch["state"].shape[2:]),
                 batch["previous_action"].reshape(b * t), dtype)
    z = jnp.swapaxes(z.reshape((b, t, -1)), 0, 1)  # time first, for the scan
    keep = 1.0 - jnp.swapaxes(batch["done"], 0, 1).astype(dtype)

    def step(carry, xs):
        z_t, keep_t = xs
        h, c = lstm_step(p, z_t, *carry)
        # departure: the state is zeroed AFTER the step that ended
        return (h * keep_t[:, None], c * keep_t[:, None]), h

    start = (batch["initial_h"].astype(dtype), batch["initial_c"].astype(dtype))
    _, h_all = jax.lax.scan(step, start, (z, keep))
    return dueling_head(p, jnp.swapaxes(h_all, 0, 1))


def rescale(x, eps):
    """h(x) = sign(x) (sqrt(|x| + 1) - 1) + eps x (Pohlen et al. 2018)."""
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + eps * x


def rescale_inverse(x, eps):
    return jnp.sign(x) * (jnp.square(
        (jnp.sqrt(1.0 + 4.0 * eps * (jnp.abs(x) + 1.0 + eps)) - 1.0)
        / (2.0 * eps)) - 1.0)


def td_errors(online_q, target_q, batch: dict, *, burn_in: int, n_step: int,
              discount: float, rescale_eps: float):
    """n-step double-Q TD errors `[B, T - burn_in - 1]` of the supervised
    steps: burn-in is cut from the loss, not from the unroll."""
    online_q, target_q = online_q[:, burn_in:], target_q[:, burn_in:]
    action = batch["action"][:, burn_in:]
    reward = batch["reward"][:, burn_in:].astype(jnp.float32)
    gamma = discount * (1.0 - batch["done"][:, burn_in:].astype(jnp.float32))
    last = online_q.shape[1] - 1
    best = jnp.argmax(online_q, axis=-1)  # double Q: the online net chooses
    value = rescale_inverse(
        jnp.take_along_axis(target_q, best[..., None], axis=-1)[..., 0],
        rescale_eps)
    deltas = []
    for t in range(last):
        end = min(t + n_step, last)  # departure: the horizon ends with the sequence
        ret, weight = 0.0, 1.0
        for k in range(t, end):
            ret = ret + weight * reward[:, k]
            weight = weight * gamma[:, k]
        target = rescale(ret + weight * value[:, end], rescale_eps)
        taken = jnp.take_along_axis(
            online_q[:, t], action[:, t, None], axis=-1)[:, 0]
        deltas.append(jax.lax.stop_gradient(target) - taken)
    return jnp.stack(deltas, axis=1)


def loss_and_priorities(online: dict, target: dict, batch: dict, is_weight, *,
                        burn_in: int, n_step: int, discount: float,
                        rescale_eps: float, eta: float, dtype=jnp.float32):
    """-> (importance-weighted mean over time of the squared TD error,
    priorities eta max|d| + (1 - eta) mean|d| per sequence, Q-values)."""
    online_q = q_values(online, batch, dtype)
    delta = td_errors(online_q, q_values(target, batch, dtype), batch,
                      burn_in=burn_in, n_step=n_step, discount=discount,
                      rescale_eps=rescale_eps)
    loss = jnp.mean(jnp.mean(jnp.square(delta), axis=1) * is_weight)
    ad = jnp.abs(delta)
    priorities = eta * jnp.max(ad, axis=1) + (1.0 - eta) * jnp.mean(ad, axis=1)
    return loss, (priorities, online_q)


def evaluate(online: dict, target: dict, batch: dict, is_weight, hyper: dict,
             precision: str | None = "highest", dtype=jnp.float32) -> dict:
    """Everything the comparison reads, as numpy: `q`, `loss`,
    `priorities`, `grads` (the tree of `online`) and `grad_norm`.
    `precision` is the matmuls' (`None`: the backend's default, which on
    a TPU rounds float32 operands to bfloat16); `dtype` the activations'."""

    def run(online, target, batch, is_weight):
        (loss, (priorities, q)), grads = jax.value_and_grad(
            loss_and_priorities, has_aux=True)(
                online, target, batch, is_weight, dtype=dtype, **hyper)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g in jax.tree.leaves(grads)))
        return {"q": q, "loss": loss, "priorities": priorities,
                "grads": grads, "grad_norm": norm}

    with jax.default_matmul_precision(precision or "default"):
        return jax.device_get(jax.jit(run)(online, target, batch, is_weight))


# -- the prioritized sampler and the ring, in numpy ---------------------------


def priority(errors: np.ndarray) -> np.ndarray:
    return np.power(np.abs(errors.astype(np.float64)) + PER_EPS, PER_ALPHA)


def importance_weights(priorities: np.ndarray, size: int, beta: float,
                       idx: np.ndarray) -> np.ndarray:
    """`(N p_i / sum p) ** -beta` of the drawn slots over the batch's
    largest."""
    p = priorities.astype(np.float64)
    weights = np.power(size * p[idx] / p.sum(), -beta)
    return weights / weights.max()


def stratified_sample(priorities: np.ndarray, size: int, beta: float,
                      uniforms: np.ndarray):
    """One draw from each of n = len(uniforms) equal segments of the
    summed priorities -> (indices, their importance weights, distance of
    each draw to the nearer edge of the slot it fell in, as a share of
    the total: a float32 cumulative sum may disagree with this float64
    one only where that is tiny)."""
    p = priorities.astype(np.float64)
    cum = np.cumsum(p)
    total, n = cum[-1], len(uniforms)
    u = (np.arange(n) + uniforms.astype(np.float64)) * (total / n)
    idx = np.minimum(np.searchsorted(cum, u, side="right"), len(p) - 1)
    edge = np.minimum(np.abs(cum[idx] - u), np.abs(u - (cum[idx] - p[idx])))
    return idx, importance_weights(priorities, size, beta, idx), edge / total


def ring_write(storage: dict, priorities: np.ndarray, ptr: int, size: int,
               new: dict, errors: np.ndarray):
    """Oldest first: `new` (leading dimension W) goes in at `ptr`, which
    then moves on by W around the ring -> (storage, priorities, ptr, size)."""
    capacity, width = len(priorities), len(errors)
    slots = (ptr + np.arange(width)) % capacity
    storage = {k: v.copy() for k, v in storage.items()}
    for k, v in new.items():
        storage[k][slots] = v
    priorities = priorities.copy()
    priorities[slots] = priority(errors)
    return storage, priorities, (ptr + width) % capacity, min(size + width, capacity)
