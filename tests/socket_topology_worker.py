"""One process of the full socket-topology multi-host test.

Run as: python socket_topology_worker.py learner <pid> <updates> <args...>
        python socket_topology_worker.py actor <task> <learner_index> <args...>

Unlike multihost_worker.py (which drives learner internals directly),
this drives `runtime.transport.run_role` — the REAL deployment entry the
CLI launchers call — so the whole lived-in topology is under test: two
learner processes jointly pjit-ing over a global (2 x 4 virtual CPU
device) mesh, each serving its own socket data plane on port+pid, with
socket actor processes partitioned across them, checkpointing, and
restart-resume. The closest analogue of the reference's cluster mode
(`/root/reference/train_impala.py:31-35`).
"""

import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")  # whatever the parent's environment says

role = sys.argv[1]

if role == "learner":
    jax.config.update("jax_num_cpu_devices", 4)
    pid = int(sys.argv[2])
    updates = int(sys.argv[3])
    config_path = sys.argv[4]
    section = sys.argv[5]
    ckpt_dir = sys.argv[6]
    # DRL_COORDINATOR / DRL_NUM_PROCESSES are in the env; the pid is ours.
    os.environ["DRL_PROCESS_ID"] = str(pid)
else:
    task = int(sys.argv[2])
    os.environ["DRL_LEARNER_INDEX"] = sys.argv[3]
    config_path = sys.argv[4]
    section = sys.argv[5]

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from distributed_reinforcement_learning_tpu.runtime.transport import run_role

if role == "learner":
    run_role("impala", config_path, section, mode="learner", task=-1,
             num_updates=updates, seed=7, checkpoint_dir=ckpt_dir,
             checkpoint_interval=5)
    # Lockstep evidence for the driver test: the global pjit collectives
    # force every process through the same number of steps.
    print(f"RESULT {pid} final_ok", flush=True)
else:
    run_role("impala", config_path, section, mode="actor", task=task,
             num_updates=10**9, seed=100 + task, actor_grace=180.0)
