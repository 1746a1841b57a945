"""Irregular-workload example (paper §8.2.2) + the hybrid addressing story.

Runs histogram-equalization — the paper's reduction-heavy irregular app —
through the kernel layer, and demonstrates the p_local effect: the same
logical computation placed with SEQUENTIAL vs INTERLEAVED region policies,
with the traffic difference predicted by the interconnect model.

The kernel call goes through a kernel-only `Cluster` (no model attached):
its scoped `KernelPolicy` picks the blocking (autotuned, registry-cached)
and records the dispatch traffic.

    PYTHONPATH=src python examples/locality_pipeline.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp

from repro.cluster import Cluster
from repro.core.interconnect import TOP_H, TopologyModel
from repro.kernels import ops
from repro.runtime.compile_cache import use_persistent_cache


def histogram_equalization(img: jax.Array, bins: int = 256) -> jax.Array:
    """Paper §8.2.2: contrast enhancement via the intensity CDF.

    Reductions (histogram) + serial step (CDF) + parallel map (LUT apply) —
    the structure that stresses synchronization on MemPool.
    """
    flat = img.reshape(-1)
    hist = jnp.zeros((bins,), jnp.int32).at[flat].add(1)     # reduction
    cdf = jnp.cumsum(hist)                                   # serial scan
    cdf_min = cdf[jnp.argmax(cdf > 0)]
    denom = jnp.maximum(flat.size - cdf_min, 1)
    lut = jnp.round((cdf - cdf_min) / denom * (bins - 1)).astype(jnp.uint8)
    return lut[flat].reshape(img.shape)                      # parallel map


def main():
    use_persistent_cache()
    key = jax.random.PRNGKey(0)
    # synthetic low-contrast image
    img = jnp.clip(
        (jax.random.normal(key, (512, 512)) * 20 + 100), 0, 255
    ).astype(jnp.int32)
    eq = jax.jit(histogram_equalization)(img)
    spread_before = int(img.max() - img.min())
    spread_after = int(eq.max() - eq.min())
    print(f"histogram equalization: intensity spread {spread_before} -> "
          f"{spread_after} (full range = 255)")
    assert spread_after > spread_before

    # follow with the paper's 2dconv on the equalized image, dispatched
    # through a kernel-only Cluster's policy (autotuned blocking on miss)
    cluster = Cluster()
    w = jnp.asarray([[1, 2, 1], [2, 4, 2], [1, 2, 1]], jnp.float32) / 16
    with cluster.policy("tuned") as pol:
        smoothed = ops.tuned_call("conv2d", eq.astype(jnp.float32), w)
    print(f"smoothed via Pallas conv2d: mean {float(smoothed.mean()):.1f} "
          f"(policy={pol.mode}, stats={dict(pol.stats)})")

    # the p_local story on this workload: the LUT-apply phase is fully
    # local (SEQUENTIAL region); the histogram reduction is all-remote
    # (INTERLEAVED). The interconnect model quantifies the difference:
    m = TopologyModel(TOP_H)
    for phase, p_local in [("lut_apply (sequential)", 0.95),
                           ("histogram (interleaved)", 0.02)]:
        lat = m.avg_latency(0.3, p_local=p_local)
        acc = m.accepted_load(1.0, p_local=p_local)
        print(f"  {phase:28s} p_local={p_local:.2f} -> "
              f"latency={lat:.1f}cyc, accepted={acc:.2f} req/core/cyc")


if __name__ == "__main__":
    main()
