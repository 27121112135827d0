"""Transformer-R2D2 agent: attention-based recurrent replay.

Fourth algorithm family, extending the reference's three: R2D2's
distributed prioritized sequence replay (`/root/reference/agent/r2d2.py`,
`train_r2d2.py`) with the LSTM swapped for the causal transformer of
`models/transformer_net.py`. All replay-side semantics are kept
identical to the in-tree R2D2 agent so the two are drop-in alternates
behind the same runners/queues:

- burn-in: first `burn_in` steps sliced out of the loss, not the forward
  (`agent/r2d2.py:64-68`) — for a transformer they serve as attention
  context exactly as they warm the LSTM state;
- double-Q over sequences + value rescaling on the target
  (`agent/r2d2.py:70-87`); loss = IS-weighted mean over time of squared
  TD; priority = |mean TD| (`agent/r2d2.py:151-153`); plain Adam.

What replaces the stored (h, c): nothing needs storing — the sequence
IS the state. Acting runs the same forward over a rolling window of the
last `seq_len` steps (the actor keeps the window host-side); training
attends over the stored sequence with episode-segment masking standing
in for done-masked carry resets.

Long context is where this family pays: `seq_len` is a knob, and with
`attention="ring"|"ulysses"` + a mesh whose `seq` axis > 1 the learn
step shards the sequence dimension over devices
(`parallel/sequence.py`), which no recurrent model can do.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from distributed_reinforcement_learning_tpu.agents import common
from distributed_reinforcement_learning_tpu.models.transformer_net import TransformerQNet


@dataclasses.dataclass(frozen=True)
class XformerConfig:
    """R2D2 replay hyperparameters + transformer size knobs."""

    obs_shape: tuple[int, ...] = (2,)
    num_actions: int = 2
    seq_len: int = 10
    burn_in: int = 5
    d_model: int = 256
    num_heads: int = 4
    num_layers: int = 2
    discount_factor: float = 0.997
    learning_rate: float = 1e-4
    rescale_eps: float = 1e-3
    dtype: Any = jnp.float32
    # "dense" on one device; "ring" / "ring_zigzag" / "ulysses" shard the
    # sequence over the mesh's `seq` axis (pass the mesh at
    # construction). "ring_zigzag" is the balanced-causal ring: the model
    # holds its residual stream in zigzag layout for the whole forward.
    attention: str = "dense"
    # Mixture-of-experts MLPs: num_experts > 0 swaps every block's dense
    # MLP for a routed MoE (`ops/moe.py`); with a mesh whose `expert`
    # axis > 1 the experts run expert-parallel. The router's
    # load-balancing loss enters the TD loss scaled by moe_aux_weight.
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 1e-2
    # Pipeline parallelism: the learn step runs the blocks as GPipe
    # stages over the mesh's `pipe` axis (`parallel/pipeline.py`), each
    # stage owning num_layers/stages contiguous layers, splitting each
    # batch into this many microbatches. Uses the stacked-param body
    # (dense attention; exclusive with ring/ulysses and MoE).
    pipeline: bool = False
    pipeline_microbatches: int = 2
    # Number of pipeline stages (devices on the `pipe` axis); 0 means
    # one stage per layer, otherwise >= 2 and it must divide num_layers
    # (virtual stages).
    pipeline_stages: int = 0
    # Rematerialize each transformer block in the backward pass
    # (jax.checkpoint) — activation memory stops growing with
    # num_layers x seq_len at the cost of ~one extra forward.
    remat: bool = False
    # Stacked [num_layers, ...] param layout WITHOUT the pipeline
    # schedule (plain scan over layers). pipeline=True implies it; set
    # it alone on actor twins so they share a pipelined learner's
    # checkpoint/weight layout.
    stacked: bool = False
    # None = the reference's |mean TD| sequence priority (parity quirk);
    # a float (paper: 0.9) = eta*max|TD| + (1-eta)*mean|TD| stable mode
    # (common.SequenceReplayLearnMixin._seq_priority).
    priority_eta: float | None = None
    # None = plain unclipped Adam (R2D2-family reference parity); a float
    # adds global-norm clipping (stable mode, config key adam_clip_norm).
    gradient_clip_norm: float | None = None


class XformerBatch(NamedTuple):
    """Sequence batch — the R2D2 queue payload minus the stored (h, c)."""

    state: jax.Array  # [B, T, *obs]
    previous_action: jax.Array  # [B, T] i32
    action: jax.Array  # [B, T] i32
    reward: jax.Array  # [B, T] f32
    done: jax.Array  # [B, T] bool


def build_transformer_models(cfg, mesh, *, seq_len: int, head: str = "dueling_q"):
    """(model, plain_apply_twin) for any transformer-family config.

    Shared by the Transformer-R2D2 and Transformer-IMPALA agents: `cfg`
    supplies the body knobs (attention / num_experts+moe_* / pipeline* /
    stacked / remat / d_model / num_heads / num_layers / num_actions /
    dtype); `head` picks the output head. The twin applies the SAME
    params without collective schedules or sharding constraints — for
    acting on rolling windows and for scoring ragged ingest batches —
    and is the model itself when no sharded feature is on.
    """
    attention_fn = None
    sequence_perm = None
    if cfg.attention != "dense":
        if mesh is None:
            raise ValueError(f"attention={cfg.attention!r} needs a mesh")
        from distributed_reinforcement_learning_tpu.parallel import sequence as sp
        from distributed_reinforcement_learning_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS

        fns = {
            "ring": sp.ring_attention,
            # pre_permuted: the MODEL holds its stream in zigzag
            # layout for the whole forward (one reorder, not one per
            # layer) via sequence_perm below.
            "ring_zigzag": functools.partial(
                sp.ring_attention, schedule="zigzag", pre_permuted=True),
            "ulysses": sp.ulysses_attention,
        }
        if cfg.attention not in fns:
            raise ValueError(
                f"unknown attention {cfg.attention!r}; one of "
                f"['dense', {', '.join(map(repr, fns))}]")
        attention_fn = functools.partial(
            lambda f, q, k, v, segs: f(
                mesh, q, k, v, causal=True, batch_axis=DATA_AXIS, segment_ids=segs
            ),
            fns[cfg.attention],
        )
        if cfg.attention == "ring_zigzag":
            sequence_perm = sp.zigzag_permutation(seq_len, mesh.shape[SEQ_AXIS])
    moe_mesh = None
    if cfg.num_experts and mesh is not None:
        from distributed_reinforcement_learning_tpu.parallel.mesh import EXPERT_AXIS

        if mesh.shape.get(EXPERT_AXIS, 1) > 1:
            moe_mesh = mesh
    pipeline_mesh = None
    if cfg.pipeline:
        if mesh is None:
            raise ValueError("pipeline=True needs a mesh with a 'pipe' axis")
        if cfg.attention != "dense" or cfg.num_experts:
            raise ValueError(
                "pipeline is exclusive with sequence-parallel attention and MoE")
        if cfg.pipeline_stages < 0 or cfg.pipeline_stages == 1:
            raise ValueError(
                f"pipeline_stages must be 0 (one stage per layer) or >= 2, "
                f"got {cfg.pipeline_stages}")
        from distributed_reinforcement_learning_tpu.parallel.mesh import PIPE_AXIS

        want = cfg.pipeline_stages or cfg.num_layers
        if cfg.num_layers % want != 0:
            raise ValueError(
                f"pipeline_stages={cfg.pipeline_stages} must divide "
                f"num_layers={cfg.num_layers}")
        have = mesh.shape.get(PIPE_AXIS, 1)
        if have != want:
            raise ValueError(
                f"mesh pipe axis is {have} but the config asks for "
                f"{want} stages (pipeline_stages={cfg.pipeline_stages}, "
                f"num_layers={cfg.num_layers})")
        pipeline_mesh = mesh
    make_model = lambda fn, perm=None, pipe=None, moe_mesh=moe_mesh: TransformerQNet(
        num_actions=cfg.num_actions,
        d_model=cfg.d_model,
        num_heads=cfg.num_heads,
        num_layers=cfg.num_layers,
        max_len=max(seq_len, 16),
        dtype=cfg.dtype,
        attention_fn=fn,
        sequence_perm=perm,
        num_experts=cfg.num_experts,
        moe_top_k=cfg.moe_top_k,
        moe_capacity_factor=cfg.moe_capacity_factor,
        moe_mesh=moe_mesh,
        stack_layers=cfg.pipeline or cfg.stacked,
        pipeline_mesh=pipe,
        pipeline_microbatches=cfg.pipeline_microbatches,
        remat=cfg.remat,
        head=head,
    )
    model = make_model(attention_fn, sequence_perm, pipeline_mesh)
    # Plain-apply twin over the SAME params — see docstring. (For the
    # pipelined model the twin keeps stack_layers — same param layout —
    # but applies the stages with the plain scan; for expert-parallel
    # MoE it drops the sharding constraints.)
    twin = (
        make_model(None, moe_mesh=None)
        if (attention_fn is not None or pipeline_mesh is not None or moe_mesh is not None)
        else model
    )
    return model, twin


def init_transformer_params(model, cfg, mesh, *, seq_len: int, rng):
    """Trainable params for any transformer-family model.

    The dummy init batch must cover the mesh's data axis (sharded
    forwards run through shard_map at init too) and, when pipelined,
    split into microbatches; sown collections (MoE aux losses) are
    dropped so only trainables reach the optimizer. Shared by both
    transformer agents so the sizing rule cannot drift.
    """
    b = 1 if mesh is None else mesh.shape.get("data", 1)
    if cfg.pipeline:
        b *= cfg.pipeline_microbatches
    obs = jnp.zeros((b, seq_len, *cfg.obs_shape), jnp.float32)
    pa = jnp.zeros((b, seq_len), jnp.int32)
    done = jnp.zeros((b, seq_len), bool)
    variables = model.init(rng, obs, pa, done)
    return {"params": variables["params"]}


class XformerAgent(common.SequenceReplayLearnMixin):
    def __init__(self, cfg: XformerConfig, mesh=None):
        self.cfg = cfg
        self._mesh = mesh
        self.model, self._dense_model = build_transformer_models(
            cfg, mesh, seq_len=cfg.seq_len)
        self.tx = common.adam_with_clip(cfg.learning_rate,
                                        clip_norm=cfg.gradient_clip_norm)
        self.act = jax.jit(self._act)
        self.td_error = jax.jit(self._td_error)
        self.learn = jax.jit(self._learn, donate_argnums=(0,))
        self.learn_many = jax.jit(
            common.scan_learn_weighted(self._learn), donate_argnums=(0,)
        )
        self.sync_target = jax.jit(lambda s: s.sync_target())

    def init_state(self, rng: jax.Array) -> common.TargetTrainState:
        params = init_transformer_params(
            self.model, self.cfg, self._mesh, seq_len=self.cfg.seq_len, rng=rng)
        return common.TargetTrainState.create(params, self.tx)

    # -- act ---------------------------------------------------------------
    def _act(self, params, obs_win, prev_action_win, done_win, epsilon, rng):
        """Batched epsilon-greedy over the LAST step of a rolling window.

        `obs_win [N, W, *obs]`: the actor's recent history, a window the
        actor maintains host-side — the transformer counterpart of
        carrying (h, c) between steps.

        Acting always runs the plain-apply twin: a rolling window is
        small and host-local, where the learn step's collective
        schedules (ring/pipeline shard_maps) are wrong or impossible —
        same params, same math, no mesh.
        """
        q_seq = self._dense_model.apply(
            params, common.normalize_obs(obs_win, self.cfg.dtype), prev_action_win, done_win)
        q = q_seq[:, -1]
        action = common.epsilon_greedy(q, epsilon, self.cfg.num_actions, rng)
        return action, q

    # -- shared sequence target math --------------------------------------
    # _td_error/_loss/_learn come from SequenceReplayLearnMixin; this
    # supplies the transformer forward. Replay semantics live in
    # `common.sequence_double_q_td` — shared with the LSTM agent so the
    # two families cannot drift.
    def _sequence_td(self, params, target_params, batch: XformerBatch, model=None,
                     unroll_scope: str | None = None):
        del unroll_scope  # attention has no sequential recurrence to name
        cfg = self.cfg
        model = model or self.model
        obs = common.normalize_obs(batch.state, self.cfg.dtype)
        forward = lambda p: model.apply(p, obs, batch.previous_action, batch.done)
        discounts = (~batch.done).astype(jnp.float32) * cfg.discount_factor
        if cfg.num_experts:
            # The online forward collects the MoE routers' sown
            # load-balancing terms; the target forward doesn't need them.
            main_q, sown = model.apply(
                params, obs, batch.previous_action, batch.done, mutable=["losses"])
            aux = cfg.moe_aux_weight * sum(
                jnp.asarray(x) for x in jax.tree.leaves(sown.get("losses", {})))
            tv, sav = common.sequence_double_q_td(
                main_q, forward(target_params), batch.action, batch.reward,
                discounts, burn_in=cfg.burn_in, rescale_eps=cfg.rescale_eps)
            return tv, sav, aux
        return common.sequence_double_q_td(
            forward(params), forward(target_params), batch.action, batch.reward,
            discounts, burn_in=cfg.burn_in, rescale_eps=cfg.rescale_eps)

    def _td_error(self, state: common.TargetTrainState, batch: XformerBatch):
        tv, sav = self._sequence_td(
            state.params, state.target_params, batch, model=self._dense_model)[:2]
        return jnp.abs(jnp.mean(tv - sav, axis=1))
