"""Token recall as a pure-JAX env: the jittable stand-in that carries a
language model's vocabulary through the fused loop
(`runtime/anakin_tokens.py`).

Every step the env shows one token x_t, drawn uniformly from [0, V)
from the env's OWN key (folded with the step); the action is a token;
the reward is 1 if a_t == x_{t - distance} (t >= distance) else 0. An
episode is exactly `episode_len` steps, then `done` and a fresh key.

Stated for what it is: an env whose cost per step is nil, so that a cell
built on it times the model. Whether anything is LEARNED on it is not
claimed (a policy has to copy a token seen `distance` steps ago out of a
vocabulary of V: the attention pattern it asks for is one induction
head).

Follows the `cartpole_jax` contract (`OBS_SHAPE`, `NUM_ACTIONS`,
`reset(rng, n) -> (state, obs)`, `step(state, actions, rng) -> (state,
obs, reward, done, episode_return)`) as an object, because the
vocabulary and the episode length are the section's.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp


class TokenRecallState(NamedTuple):
    key: jax.Array  # [N, 2] u32 each env's own key, fresh every episode
    t: jax.Array  # [N] i32 step inside the episode
    shown: jax.Array  # [N] i32 x_t, the token on show
    history: jax.Array  # [N, distance] i32 ring of x_{t-distance} .. x_{t-1}
    returns: jax.Array  # [N] f32 accumulated episode return


@dataclasses.dataclass(frozen=True)
class TokenRecall:
    vocab: int
    episode_len: int
    distance: int = 8

    OBS_SHAPE = ()  # one token id

    @property
    def NUM_ACTIONS(self) -> int:  # noqa: N802 — the env contract's name
        return self.vocab

    def _draw(self, key: jax.Array, t: jax.Array) -> jax.Array:
        return jax.vmap(lambda k, i: jax.random.randint(
            jax.random.fold_in(k, i), (), 0, self.vocab, jnp.int32))(key, t)

    def reset(self, rng: jax.Array, num_envs: int):
        key = jax.random.split(rng, num_envs)
        t = jnp.zeros(num_envs, jnp.int32)
        shown = self._draw(key, t)
        state = TokenRecallState(
            key=key, t=t, shown=shown,
            history=jnp.zeros((num_envs, self.distance), jnp.int32),
            returns=jnp.zeros(num_envs, jnp.float32))
        return state, shown

    def step(self, state: TokenRecallState, actions: jax.Array, rng: jax.Array):
        """-> (state', obs', reward, done, episode_return); `obs'` is the
        first token of the next episode where `done`. `rng` is unused:
        every draw comes from the env's own key."""
        del rng
        slot = (state.t % self.distance)[:, None]
        target = jnp.take_along_axis(state.history, slot, axis=1)[:, 0]
        reward = ((actions == target) & (state.t >= self.distance)
                  ).astype(jnp.float32)
        history = jnp.where(
            jnp.arange(self.distance)[None] == slot, state.shown[:, None],
            state.history)
        t = state.t + 1
        done = t >= self.episode_len
        returns = state.returns + reward
        # A fresh key for the next episode, from the env's own.
        fresh = jax.vmap(lambda k: jax.random.fold_in(k, self.episode_len))(
            state.key)
        key = jnp.where(done[:, None], fresh, state.key)
        t = jnp.where(done, 0, t)
        shown = self._draw(key, t)
        new_state = TokenRecallState(
            key=key, t=t, shown=shown, history=history,
            returns=jnp.where(done, 0.0, returns))
        return new_state, shown, reward, done, jnp.where(done, returns, 0.0)
