"""The one vocabulary of names for a profile of this program.

Device scopes go through `jax.named_scope` inside the compiled steps:
they are `op_name` metadata on every HLO instruction (fusions too), so
they cost nothing at run time and the profiler's `hlo_stats` gives them
back per op. Host spans go through `observability.trace.chip_span`
around the fused loops' chunks. Nothing else in the program spells
these strings; a reader of a profile (docs/performance.md
"Observability", the benchmark's per-layer metrics) matches on them.
The same call feeds `observability.trace.HOST_RECORD`, the process's own
record of its host spans on the wall clock, which the fused launchers
print in their log: the start's spans at the end of this file are its.

A nested scope is written out in full (`collect/env/render`, not
`render`): a `lax.scan` or a `jax.jit` in between puts `while/body` or
`jit(step)` into the path, so only a name that carries its own parents
is found again as one substring. The deepest name in an op's path is the
scope the op belongs to. Backward ops of the loss appear under
`transpose(jvp(learn/loss))`, forward ops under `jvp(learn/loss)`.
"""

from __future__ import annotations

import functools

# Scope names are metadata, and JAX (0.9.0) leaves metadata out of the
# key of its persistent compile cache. A cache directory that another
# commit filled therefore hands back THAT commit's executable, with its
# names (or none): seen here as a profile with no scope in it. The name
# of a jitted function is the HLO module's name, which IS in the key, so
# the jitted steps that carry scopes go through `tagged`. Bump the tag
# when a scope is added, renamed or moved; nothing else reads it.
CACHE_TAG = "s4"


def tagged(fn):
    """`fn` under the name `<name>_<CACHE_TAG>`, for `jax.jit`."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        return fn(*args, **kwargs)

    wrapper.__name__ = f"{fn.__name__}_{CACHE_TAG}"
    return wrapper


# -- device scopes --------------------------------------------------------
COLLECT = "collect"  # the scan over env steps: loop control + stacked outputs
ACT = "collect/act"  # obs prep, torso, LSTM, sampling (`agent._act`)
ENV = "collect/env"  # env dynamics (`env.step` less rendering)
RENDER = "collect/env/render"  # raw screen, 2-frame max, luma, resize, history
RECORD = "collect/record"  # the per-step record + carry of the rollout
# [T, B, ...] rollout -> [B, T, ...] batch: `AnakinImpala` under a mesh
# only. The one-chip chunk learns time-major (PR 29): no op has this name.
TO_BATCH_MAJOR = "to_batch_major"
REPLAY = "replay"  # device ring: ingest, sample, priority write-back
# TD error of the new sequences: the target net's unroll alone. The online
# net's Q-values are `collect/act`'s own, stacked by the collect scan.
REPLAY_SCORE = "replay/score"
REPLAY_WRITE = "replay/write"  # the ring write at `ptr`
REPLAY_SAMPLE = "replay/sample"  # cumsum, stratified search, batch gather
REPLAY_PRIORITIES = "replay/priorities"  # write-back of sampled priorities
LEARN = "learn"  # all of one optimizer step
LOSS = "learn/loss"  # forward (backward: transpose(jvp(learn/loss)))
UNROLL = "learn/loss/unroll"  # the LSTM recurrence alone, learn step only
VTRACE = "learn/vtrace"  # V-trace targets (Pallas kernel on the TPU)
OPTIMIZER = "learn/optimizer"  # optimizer update + parameter add

# The token loop (runtime/anakin_tokens.py, models/looped_lm.py):
ACT_LOOP = "collect/act/loop"  # one decode step's R x L layer passes
ACT_CACHE = "collect/act/cache"  # key/value writes at t and the cache reads
ACT_HEAD = "collect/act/head"  # final norm, vocabulary head, sampling
LOOP = "learn/loss/loop"  # the looped stack (backward: re-entered under transpose)
HEADS = "learn/loss/heads"  # R x (norm, vocabulary, gate, value, log-softmax)
LOSS_VTRACE = "learn/loss/vtrace"  # per-pass V-trace on taken-action log-probs

# The hybrid state-space / attention model in the same loop
# (models/hybrid_lm.py, ops/ssd.py); heads, cache, V-trace and optimizer
# under the token loop's names above. No bump of CACHE_TAG for these: no
# older commit compiled a chunk of this model, so no cache directory
# holds one without them, and a bump would start every other chunk cold.
ACT_LAYERS = "collect/act/layers"  # a decode step's projections and MLPs
ACT_SSM = "collect/act/ssm"  # window shift, state update and read-out, nine layers
LAYERS = "learn/loss/layers"  # the stack (backward: re-entered under transpose)
SSD = "learn/loss/layers/ssd"  # the chunked scan alone
ATTENTION = "learn/loss/layers/attention"  # the one attention mixer

# The sparse-expert hybrid model in the same loop (models/moe_lm.py,
# ops/gated_delta.py, ops/expert_share.py); layers, attention, cache,
# heads, V-trace and optimizer under the names above. No bump of
# CACHE_TAG, for the reason given there.
ACT_GDN = "collect/act/gdn"  # window shift, delta-rule state update and read-out
ACT_MOE = "collect/act/moe"  # a decode step's expert MLPs: norm, shared expert, the sum
ACT_MOE_ROUTE = "collect/act/moe/route"  # router product, softmax, top-k; the record
ACT_MOE_EXPERTS = "collect/act/moe/experts"  # sort, gather, grouped products, add
GDN = "learn/loss/layers/gdn"  # the chunked delta rule alone
MOE_ROUTE = "learn/loss/layers/moe/route"  # router product, softmax, top-k
MOE_EXPERTS = "learn/loss/layers/moe/experts"  # sort, gather, grouped products, add
MOE_SHARED = "learn/loss/layers/moe/shared"  # the gated shared expert
MOE_ACT = {"route": ACT_MOE_ROUTE, "experts": ACT_MOE_EXPERTS, "shared": ACT_MOE}
MOE_LEARN = {"route": MOE_ROUTE, "experts": MOE_EXPERTS, "shared": MOE_SHARED}

# The latent-attention sparse-expert model in the same loop
# (models/latent_moe_lm.py, ops/latent_attention.py, ops/expert_share.py);
# layers, cache, the expert scopes, heads, V-trace and optimizer under
# the names above. No bump of CACHE_TAG, for the reason given there.
ACT_MLA = "collect/act/mla"  # a decode step's latent attention, every layer
ACT_MLA_PROJECT = "collect/act/mla/project"  # q and latent down/up-projections, norms, rotary, W_o
ACT_MLA_ATTEND = "collect/act/mla/attend"  # absorb W^UK, scores and weighted sum on the cache, W^UV
MLA_PROJECT = "learn/loss/layers/mla/project"  # W_qa, W_qb, W_kva, their norms, W_o
MLA_ATTEND = "learn/loss/layers/mla/attend"  # W_kvb, rotary, the causal attention core
DENSE = "learn/loss/layers/dense"  # the leading layer's dense SwiGLU
MTP = "learn/loss/mtp"  # the multi-token-prediction module, its layer and its head
MLA_ACT = {"project": ACT_MLA_PROJECT, "attend": ACT_MLA_ATTEND,
           "dense": ACT_LAYERS, **MOE_ACT}
MLA_LEARN = {"project": MLA_PROJECT, "attend": MLA_ATTEND, "dense": DENSE,
             **MOE_LEARN}
MLA_MTP = {"project": f"{MTP}/mla/project", "attend": f"{MTP}/mla/attend",
           "route": f"{MTP}/moe/route", "experts": f"{MTP}/moe/experts",
           "shared": f"{MTP}/moe/shared"}

# The gated-short-convolution sparse-expert model in the same loop
# (models/conv_moe_lm.py, ops/expert_share.py); layers, the attention
# mixer, cache, the dense layer, the expert scopes (no shared expert),
# heads, V-trace and optimizer under the names above. No bump of
# CACHE_TAG, for the reason given there.
ACT_CONV = "collect/act/conv"  # the in-projection's split, both gates, the window shift, the taps
CONV = "learn/loss/layers/conv"  # the whole convolution mixer: W_in, both gates, the taps, W_out
CONV_ACT = {"dense": ACT_LAYERS, "route": ACT_MOE_ROUTE, "experts": ACT_MOE_EXPERTS}
CONV_LEARN = {"dense": DENSE, "route": MOE_ROUTE, "experts": MOE_EXPERTS}

# The sliding-window / global-attention sparse-expert model in the same
# loop (models/window_moe_lm.py); layers, the global layer's cache, the
# expert scopes (the route ahead of attention; no shared expert), heads,
# V-trace and optimizer under the names above. No bump of CACHE_TAG, for
# the reason given there.
ACT_RING = "collect/act/ring"  # a window layer's ring: the write at t mod W, scores and weighted sum on min(span, W) slots (the global layer's on its prefix: ACT_CACHE)
GLOBAL_ATTENTION = "learn/loss/layers/global_attention"  # the NoPE layer: q, k, v, the full causal core
WINDOW_ATTENTION = "learn/loss/layers/window_attention"  # a window layer: q, k, v, rotary, the windowed core

# The state-space / sparse-expert / attention model whose layers are one
# sublayer each, in the same loop (models/ssm_moe_lm.py); the state-space
# mixer's act-time scope and chunked scan, the convolution, the NoPE
# attention layer and its cache, the expert scopes (route, held experts,
# the UNGATED shared expert), heads, V-trace and optimizer under the names
# above. No bump of CACHE_TAG, for the reason given there.
ACT_ATTEND = "collect/act/attend"  # the attention layer of a decode step: q, k, v, W_o around its cache's scope

IMPALA_CHUNK_SCOPES = (COLLECT, ACT, ENV, RENDER, RECORD,
                       LEARN, LOSS, VTRACE, OPTIMIZER)
REPLAY_CHUNK_SCOPES = (COLLECT, REPLAY, LEARN)
R2D2_CHUNK_SCOPES = (ACT, ENV, RECORD, REPLAY_SCORE, REPLAY_WRITE,
                     REPLAY_SAMPLE, REPLAY_PRIORITIES, LOSS, UNROLL, OPTIMIZER)
TOKENS_CHUNK_SCOPES = (ENV, ACT, ACT_LOOP, ACT_CACHE, ACT_HEAD, LOOP, HEADS,
                       LOSS_VTRACE, OPTIMIZER)
HYBRID_CHUNK_SCOPES = (ENV, ACT, ACT_LAYERS, ACT_SSM, ACT_CACHE, ACT_HEAD,
                       LAYERS, SSD, ATTENTION, HEADS, LOSS_VTRACE, OPTIMIZER)

MOE_CHUNK_SCOPES = (ENV, ACT, ACT_LAYERS, ACT_GDN, ACT_MOE, ACT_MOE_ROUTE,
                    ACT_MOE_EXPERTS, ACT_CACHE, ACT_HEAD, LAYERS, GDN, ATTENTION,
                    MOE_ROUTE, MOE_EXPERTS, MOE_SHARED, HEADS, LOSS_VTRACE,
                    OPTIMIZER)

MLA_CHUNK_SCOPES = (ENV, ACT, ACT_LAYERS, ACT_MLA_PROJECT, ACT_MLA_ATTEND,
                    ACT_MOE, ACT_MOE_ROUTE, ACT_MOE_EXPERTS, ACT_CACHE, ACT_HEAD,
                    LAYERS, MLA_PROJECT, MLA_ATTEND, DENSE, MOE_ROUTE,
                    MOE_EXPERTS, MOE_SHARED, MTP, *MLA_MTP.values(), HEADS,
                    LOSS_VTRACE, OPTIMIZER)

CONV_CHUNK_SCOPES = (ENV, ACT, ACT_LAYERS, ACT_CONV, ACT_MOE_ROUTE,
                      ACT_MOE_EXPERTS, ACT_CACHE, ACT_HEAD, LAYERS, CONV, ATTENTION,
                      DENSE, MOE_ROUTE, MOE_EXPERTS, HEADS, LOSS_VTRACE, OPTIMIZER)

SWA_CHUNK_SCOPES = (ENV, ACT, ACT_LAYERS, ACT_RING, ACT_CACHE, ACT_MOE_ROUTE,
                    ACT_MOE_EXPERTS, ACT_HEAD, LAYERS, GLOBAL_ATTENTION,
                    WINDOW_ATTENTION, MOE_ROUTE, MOE_EXPERTS, HEADS, LOSS_VTRACE,
                    OPTIMIZER)

SSMOE_CHUNK_SCOPES = (ENV, ACT, ACT_LAYERS, ACT_SSM, ACT_ATTEND, ACT_CACHE,
                      ACT_MOE, ACT_MOE_ROUTE, ACT_MOE_EXPERTS, ACT_HEAD, LAYERS,
                      SSD, CONV, GLOBAL_ATTENTION, MOE_ROUTE, MOE_EXPERTS,
                      MOE_SHARED, HEADS, LOSS_VTRACE, OPTIMIZER)

# -- host spans of the fused loops (runtime/launch.py) ---------------------
STEP_READ = "anakin/step_read"  # int(state.train.step) at the loop head
DISPATCH = "anakin/dispatch"  # the train_chunk call
WAIT = "anakin/wait"  # first blocking read of the chunk's metrics
REPORT = "anakin/report"  # host sums, gauges, the log line
CHECKPOINT = "anakin/checkpoint"

# -- host spans of the start (utils/device.py, runtime/launch.py) ----------
# Each opened where the work happens, on the wall clock, into
# `observability.trace.HOST_RECORD`; the chunk spans above follow them.
START_IMPORT = "start/import"  # the kernel's start of the process -> launch.py imported
START_BACKEND = "start/backend"  # `open_devices`: the first call opens the backend
START_BUILD = "start/build"  # config, agent, env, the `Anakin*` constructor
START_INIT = "start/init"  # `anakin.init`: parameters and the eager `reset`
START_RESTORE = "start/restore"  # `_restore_train`
START_WARM_COLLECT = "start/warm_collect"  # the replay loops' `collect_chunk(state, warm)`
CHUNK_SPANS = (STEP_READ, DISPATCH, WAIT, REPORT, CHECKPOINT)
