"""Elastic restart: survive a node failure and resume on a smaller mesh.

Simulates the 1000-node failure path end-to-end on CPU, driving the
compiled step of a Cluster `TrainProgram` by hand:
  1. train on mesh A, async-checkpointing;
  2. "lose a host" (Coordinator event) mid-run -> preemption checkpoint;
  3. re-plan the mesh for the survivors (model axis kept, data axis shrunk);
  4. restore the same logical state onto the new mesh and keep training —
     the data stream is stateless-resumable, so not a single batch repeats.

    PYTHONPATH=src python examples/elastic_restart.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.cluster import Cluster, TrainProgram
from repro.core import compat
from repro.runtime.coordination import Coordinator, replan_mesh_shape
from repro.runtime.compile_cache import use_persistent_cache

CKPT = "/tmp/repro-elastic"


def make_batches(cfg, seq, start):
    step = start
    key = jax.random.PRNGKey(0)
    while True:
        k = jax.random.fold_in(key, step)          # stateless: f(seed, step)
        yield {"tokens": jax.random.randint(k, (4, seq), 0, cfg.vocab),
               "labels": jax.random.randint(k, (4, seq), 0, cfg.vocab)}
        step += 1


def main():
    use_persistent_cache()
    seq = 32
    cluster = Cluster("qwen3-14b-smoke")
    cfg = cluster.arch
    # compile once; drive the program's step function by hand so the
    # failure/restore choreography stays explicit
    program = cluster.compile(TrainProgram(num_steps=10, seq=seq, seed=1,
                                           checkpoint_dir=CKPT))
    state, _ = program.init_state(seed=1)
    train_step = program.step
    mgr = CheckpointManager(CKPT, keep=2)

    # phase 1: run on the "big" mesh, checkpoint every 3 steps
    coord = Coordinator(n_hosts=64)
    batches = make_batches(cfg, seq, 0)
    step = 0
    for _ in range(7):
        state, metrics = train_step(state, next(batches))
        step += 1
        if step % 3 == 0:
            mgr.save(step, state)
    print(f"phase 1: reached step {step}, loss={float(metrics['loss']):.4f}")

    # phase 2: a host dies -> coordinator replans the mesh
    coord.emit("leave", "host-17")
    new_shape = replan_mesh_shape(
        (coord.n_hosts) * 4, model_parallel=1)       # 4 chips/host, toy scale
    print(f"host lost: {coord.n_hosts} hosts remain -> new mesh {new_shape}")
    mgr.save(step, state, block=True)                # preemption checkpoint

    # phase 3: fresh process view — restore the LOGICAL state onto the
    # survivors' mesh (here: 1-device CPU mesh; layout is mesh-independent)
    latest = mgr.latest_step()
    mesh = compat.make_mesh((1,), ("data",))
    sh = jax.tree.map(
        lambda _: jax.sharding.NamedSharding(mesh,
                                             jax.sharding.PartitionSpec()),
        state)
    restored = mgr.restore(latest, jax.tree.map(jnp.zeros_like, state), sh)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float64),
                                      np.asarray(b, np.float64))
    print(f"restored step {latest} bit-exactly onto the new mesh")

    # phase 4: continue where we left off — stream is a pure f(seed, step)
    batches = make_batches(cfg, seq, latest)
    state = restored
    for _ in range(3):
        state, metrics = train_step(state, next(batches))
        latest += 1
    print(f"resumed to step {latest}, loss={float(metrics['loss']):.4f} — "
          f"no data repeated, no optimizer state lost")


if __name__ == "__main__":
    main()
