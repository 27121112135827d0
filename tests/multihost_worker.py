"""One process of the 2-process multi-host learner test (test_multihost.py).

Run as: python multihost_worker.py <process_id> <coordinator_port> <data_port>

Joins a 2-process x 4-CPU-device JAX runtime, then runs a real
`ImpalaLearner` over the GLOBAL 8-device mesh: this process dequeues its
batch_size/2 share from its own queue (the per-host half of the socket
data plane) and `place_local_batch` assembles the global batch. Prints
per-step losses; the driver test asserts both processes agree (the psum
over the global mesh makes the update identical everywhere).
"""

import sys

import jax

jax.config.update("jax_platforms", "cpu")  # whatever the parent's environment says
jax.config.update("jax_num_cpu_devices", 4)

pid = int(sys.argv[1])
coord_port = int(sys.argv[2])

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from distributed_reinforcement_learning_tpu.parallel import distributed

assert distributed.initialize(
    coordinator_address=f"localhost:{coord_port}", num_processes=2, process_id=pid
)

import numpy as np

from distributed_reinforcement_learning_tpu.agents.impala import ImpalaAgent, ImpalaConfig
from distributed_reinforcement_learning_tpu.data.fifo import TrajectoryQueue
from distributed_reinforcement_learning_tpu.parallel import make_mesh
from distributed_reinforcement_learning_tpu.runtime.impala_runner import ImpalaLearner
from distributed_reinforcement_learning_tpu.runtime.weights import WeightStore
from distributed_reinforcement_learning_tpu.utils.synthetic import synthetic_impala_batch

assert len(jax.local_devices()) == 4 and len(jax.devices()) == 8

GLOBAL_BATCH = 16
LOCAL_BATCH = GLOBAL_BATCH // jax.process_count()

cfg = ImpalaConfig(obs_shape=(4,), num_actions=2, trajectory=8, lstm_size=32,
                   start_learning_rate=1e-3, learning_frame=10**6)
mesh = make_mesh(devices=jax.devices())
queue = TrajectoryQueue(capacity=4 * LOCAL_BATCH)
weights = WeightStore()
learner = ImpalaLearner(ImpalaAgent(cfg), queue, weights, batch_size=LOCAL_BATCH,
                        rng=jax.random.PRNGKey(0), mesh=mesh)

# Each process feeds DIFFERENT local trajectories (seeded by pid) — the
# losses below still agree because the learn step sums over the global
# batch that both processes jointly assemble.
for step in range(3):
    big = synthetic_impala_batch(
        LOCAL_BATCH, cfg.trajectory, cfg.obs_shape, cfg.num_actions, cfg.lstm_size,
        seed=1000 * (pid + 1) + step,
    )
    for i in range(LOCAL_BATCH):
        queue.put(jax.tree.map(lambda x: x[i], big))
    m = learner.step(timeout=10.0)
    assert m is not None
    print(f"RESULT {pid} {step} {m['total_loss']:.6f}", flush=True)

# Weight publication must work from the global (replicated) params.
weights.flush_async()  # async-by-default publication lands in background
params, version = weights.get()
assert version == 3
assert all(np.all(np.isfinite(x)) for x in jax.tree.leaves(params))
print(f"RESULT {pid} weights_ok {float(jax.tree.leaves(params)[0].ravel()[0]):.6f}", flush=True)

# Sequence parallelism across processes: the ring's ppermute now crosses
# the process boundary (the DCN analogue). One xformer learn step over a
# (data=4, seq=2) global mesh; the losses must again agree everywhere.
from distributed_reinforcement_learning_tpu.agents.xformer import XformerAgent, XformerConfig
from distributed_reinforcement_learning_tpu.parallel import ShardedLearner
from distributed_reinforcement_learning_tpu.parallel.mesh import place_local_batch, data_sharding
from distributed_reinforcement_learning_tpu.utils.synthetic import synthetic_xformer_batch

xcfg = XformerConfig(obs_shape=(2,), num_actions=2, seq_len=8, burn_in=2,
                     d_model=32, num_heads=2, num_layers=1, attention="ring")
sp_mesh = make_mesh(devices=jax.devices(), seq_parallel=2)
xagent = XformerAgent(xcfg, mesh=sp_mesh)
xlearner = ShardedLearner(xagent, sp_mesh, num_data_args=2, num_aux_outputs=2)
xstate = xlearner.init_state(jax.random.PRNGKey(0))
GLOBAL_XB = 8
local, w_local = synthetic_xformer_batch(
    GLOBAL_XB // jax.process_count(), xcfg.seq_len, xcfg.obs_shape,
    xcfg.num_actions, seed=2000 + pid)
sharding = data_sharding(sp_mesh)
batch = place_local_batch(local, sharding)
w = place_local_batch(np.asarray(w_local), sharding)
xstate, pri, xm = xlearner.learn(xstate, batch, w)
jax.block_until_ready(xstate)
assert np.all(np.isfinite(np.asarray(pri)))
print(f"RESULT {pid} xformer_sp {float(xm['loss']):.6f}", flush=True)

# Pipeline parallelism across processes: the GPipe stage hops (ppermute
# over the `pipe` axis) now cross the process boundary — the classic
# "pipeline over DCN" placement, pipe being the lightest-traffic axis.
# 2 stages x 2 layers each over a (pipe=2, data=4) global mesh.
pcfg = XformerConfig(obs_shape=(2,), num_actions=2, seq_len=8, burn_in=2,
                     d_model=32, num_heads=2, num_layers=4, pipeline=True,
                     pipeline_stages=2, pipeline_microbatches=2)
pp_mesh = make_mesh(devices=jax.devices(), pipe_parallel=2)
pagent = XformerAgent(pcfg, mesh=pp_mesh)
plearner = ShardedLearner(pagent, pp_mesh, num_data_args=2, num_aux_outputs=2)
pstate = plearner.init_state(jax.random.PRNGKey(0))
# The pipe axis is what spans the two processes here, and the batch is
# REPLICATED over pipe (sharded only over data, which lives within each
# process). So each process supplies the full, identical global batch —
# same seed, no pid — unlike the data-split feeds above.
plocal, pw_local = synthetic_xformer_batch(
    GLOBAL_XB, pcfg.seq_len, pcfg.obs_shape, pcfg.num_actions, seed=3000)
psharding = data_sharding(pp_mesh)
pstate, ppri, pm = plearner.learn(
    pstate, place_local_batch(plocal, psharding),
    place_local_batch(np.asarray(pw_local), psharding))
jax.block_until_ready(pstate)
assert np.all(np.isfinite(np.asarray(ppri)))
print(f"RESULT {pid} xformer_pp {float(pm['loss']):.6f}", flush=True)
