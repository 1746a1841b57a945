"""The serve path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler, which is installed with jax,
compiles for a *described* v5e topology. This catches what interpret mode
cannot — a blocking that needs more VMEM than the kernel may use, a tile
the Mosaic compiler refuses — at qwen3-14b widths, in about two seconds a
kernel (the autotuner's large prefill picks take longer). Each compile
must contain the Pallas kernel itself (`tpu_custom_call`). One more test
compiles a whole paged session chunk at Falcon-H1 widths and reads what the
TPU compiler made of its recurrent state, which the CPU's compiler cannot
show.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so describing it while
collecting would break every other test worker.
"""

import re

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


# the served attention widths: query heads, kv heads (hd 128 for all)
PAGED_WIDTHS = {"qwen3-14b": (40, 8), "yi-34b": (56, 8),
                "falcon-h1-34b": (20, 4)}


@pytest.mark.parametrize("arch", sorted(PAGED_WIDTHS))
def test_paged_decode_attention_compiles_for_v5e(arch, one_chip,
                                                 no_persistent_cache):
    """The paged decode kernel at a served cell's decode shapes: 32 slots,
    129 pages a slot of 16 rows, the default pool of 4129 pages a layer
    for 8 layers, the blocking it reads from those shapes."""
    from repro.kernels.paged_attention import paged_decode_attention
    h, kv = PAGED_WIDTHS[arch]
    slots, npp, pool = 32, 129, (8 * 4129, 16, kv, HD)
    spec = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    compiled = jax.jit(paged_decode_attention).lower(
        spec((slots, 1, h, HD)), spec(pool), spec(pool),
        spec((slots,), jnp.int32), spec((slots, npp), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def _chunk_program(arch, n_layers, one_chip, monkeypatch):
    """The paged session chunk at published widths, the cells' 32 slots,
    2048 context and page 16, compiled for a described v5e with the
    Pallas kernels compiled as on the chip (off a TPU backend the policy
    would interpret them)."""
    import dataclasses

    from repro.cluster import Cluster, ServeSessionProgram
    from repro.cluster.policy import KernelPolicy
    from repro.configs import get

    monkeypatch.setattr(KernelPolicy, "interpret_for", lambda self, op: False)
    cfg = dataclasses.replace(get(arch), n_layers=n_layers)
    prog = Cluster(cfg).compile(ServeSessionProgram(
        paged=True, preempt=False, slots=32, max_seq=2048, max_prompt=2048,
        chunk=16, page_size=16))
    shaped = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    state = shaped(jax.eval_shape(prog._make_state))
    params = shaped(jax.eval_shape(prog.init_params))
    return prog._chunk_fn.lower(params, state).compile(), state


def test_paged_chunk_program_gathers_no_page_list(one_chip,
                                                  no_persistent_cache,
                                                  monkeypatch):
    """At yi-34b-d8 widths (8 layers of 56/8 heads) the chunk's attention
    is the paged decode kernel: no op holds a slot batch's whole page
    list, as pages (32, 129, 16, 8, 128) or as rows (32, 2064, 8, 128),
    and the program's temporaries stay below the 1,328,793,088 B that the
    gather-then-mask step needed."""
    compiled, _ = _chunk_program("yi-34b", 8, one_chip, monkeypatch)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    shapes = {dims for _, dims, _ in _HLO_OP.findall(text)}
    assert not shapes & {"32,129,16,8,128", "32,2064,8,128"}
    assert compiled.memory_analysis().temp_size_in_bytes < 1_328_793_088


_HLO_OP = re.compile(r"^\s*(ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(",
                     re.M)


def test_paged_chunk_program_copies_no_state(one_chip, no_persistent_cache,
                                            monkeypatch):
    """Falcon-H1's recurrent state rides in the paged decode step's layer
    scan carry and is updated in place. In the session chunk compiled for
    a v5e at published widths (2 layers, the cell's 32 slots and prime
    page count), no op copies, slices, gathers or scatters a whole SSM
    state leaf or one layer of it, and no computation yields such a buffer
    except by a dynamic-update-slice at its root (the in-place write): a
    layer's slice is read only inside the fusions that use it. (The conv
    tail, 2 MB a layer, is left out: the compiler may stage a buffer that
    small through fast memory.)"""
    import math

    compiled, state = _chunk_program("falcon-h1-34b", 2, one_chip,
                                     monkeypatch)
    text = compiled.as_text()
    leaf = state["cache"]["blocks"]["sub0"]["ssm"]
    sizes = {leaf.size, leaf.size // leaf.shape[0]}
    moved = ("copy", "copy-start", "copy-done", "slice", "gather", "scatter")
    seen, bad = 0, []
    for comp in re.split(r"\n(?=\S)", text):
        for root, dims, op in _HLO_OP.findall(comp):
            if math.prod(int(d) for d in dims.split(",") if d) not in sizes:
                continue
            seen += 1
            in_place = op == "dynamic-update-slice"
            if op in moved or in_place != bool(root):
                bad.append((dims, op, bool(root)))
    assert seen, "the state's own buffers must be found"
    assert not bad, bad
