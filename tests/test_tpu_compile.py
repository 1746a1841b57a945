"""The serve path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler, which is installed with jax,
compiles for a *described* v5e topology. This catches what interpret mode
cannot — a blocking that needs more VMEM than the kernel may use, a tile
the Mosaic compiler refuses — at qwen3-14b widths, in about two seconds a
kernel (the autotuner's large prefill picks take longer). Each compile
must contain the Pallas kernel itself (`tpu_custom_call`).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so describing it while
collecting would break every other test worker.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fused, matmul, pipeline as pp
from repro.kernels.flash_attention import flash_attention

# qwen3-14b: d_model, d_ff, query heads, kv heads, head dim
D, F, H, KV, HD = 5120, 17408, 40, 8, 128
DECODE_ROWS = 8          # one decode step of an 8-slot session
PREFILL_ROWS = 4096      # prefill-sized rows for the autotuner's picks


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


def _modeled(kernel: str, shapes: dict) -> dict:
    return dict(pp.autotune(kernel, shapes, dtype_bytes=2, mode="modeled",
                            register_record=False).blocks)


def _rmsnorm_matmul(m, n, k, blocks):
    return (lambda x, s, w: fused.rmsnorm_matmul(x, s, w, **blocks),
            ((m, k), (k,), (k, n)))


def _matmul_residual_add(m, n, k, blocks):
    return (lambda a, w, r: fused.matmul_residual_add(a, w, r, **blocks),
            ((m, k), (k, n), (m, n)))


def _matmul(m, n, k, blocks):
    return (lambda a, w: matmul.matmul(a, w, **blocks), ((m, k), (k, n)))


def _flash(*_):
    s = 2048
    return (lambda q, k, v: flash_attention(q, k, v, causal=True),
            ((1, H, s, HD), (1, KV, s, HD), (1, KV, s, HD)))


def _case(build, m, n, k, tuned: str | None = None):
    def make():
        blocks = ({} if tuned is None
                  else _modeled(tuned, {"m": m, "n": n, "k": k}))
        return build(m, n, k, blocks)
    return make


CASES = {
    # the fused decode kernels of the serve path, default blockings
    "rmsnorm_matmul-decode": _case(_rmsnorm_matmul, DECODE_ROWS, F, D),
    "matmul_residual_add-decode": _case(_matmul_residual_add, DECODE_ROWS,
                                        D, F),
    # prefill-width attention, grouped-query (40 q heads over 8 kv heads)
    "flash_attention-prefill": _case(_flash, 0, 0, 0),
    # the autotuner's modeled picks at a prefill-sized m: the VMEM budget
    # it admits blockings against is the limit the kernel compiles under
    "matmul-modeled-pick": _case(_matmul, PREFILL_ROWS, D, D, "matmul"),
    "rmsnorm_matmul-modeled-pick": _case(_rmsnorm_matmul, PREFILL_ROWS, F, D,
                                         "rmsnorm_matmul"),
    "matmul_residual_add-modeled-pick": _case(
        _matmul_residual_add, PREFILL_ROWS, D, F, "matmul_residual_add"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
