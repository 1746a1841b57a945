"""Public wrappers for the kernel suite, dispatched through the KernelPolicy.

Every public wrapper consults the active `repro.cluster.KernelPolicy`
(`current_policy()`) at call/trace time:

  * mode "reference"  -> the pure-jnp oracle (kernels/ref.py composition);
  * mode "interpret"  -> the Pallas body through the interpreter even on
                         TPU (off-TPU backends always interpret — numerics
                         identical, which is what the allclose tests
                         against ref.py verify);
  * otherwise         -> real `pl.pallas_call` lowering on TPU.

Per-op overrides (`KernelPolicy.overrides`) re-route or re-block single
ops; `tuned_call` delegates to `KernelPolicy.call`, where fused/tuned/
reference selection and autotune-on-miss live in one place.

Dispatch happens in Python, outside the inner jitted kernels (the resolved
`interpret` flag is a static jit arg), so *direct* wrapper calls always see
the policy active at that call. Inside a user-jitted function, however, the
policy is read while tracing and baked into the trace — switching the
ambient policy does NOT retrace an already-cached jit. Compiled Cluster
programs pin their policy at compile time (and the compile cache keys on
it), which is the supported way to compare policies on one model.

Every kernel registers one `OpDescriptor` in `OPS` — the single table
holding its public wrapper, its reference composition, its runtime-operand
-> pipeline-shape-dict mapping, and which operand's dtype sets the VMEM
tile footprint. The fused kernels (kernels/fused.py) register here too, so
`tuned_call` serves fused and unfused names uniformly.

The fused wrappers carry a `custom_vjp`: the forward runs the fused Pallas
kernel; the backward recomputes through the jnp reference composition
(FlashAttention-style — residuals are the kernel *inputs*, so the fused
intermediate stays out of HBM in the forward pass, which is where the
serve path and the activation-bound training forward spend their traffic).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.cluster.policy import current_policy

from . import axpy as _axpy
from . import conv2d as _conv2d
from . import dct8x8 as _dct8x8
from . import dotp as _dotp
from . import flash_attention as _fa
from . import fused as _fused
from . import matmul as _matmul
from . import paged_attention as _pa
from . import ref as _ref
from . import rmsnorm as _rmsnorm


def _take_reference(name: str) -> bool:
    """Reference-mode short-circuit for `name` under the active policy."""
    pol = current_policy()
    if pol.mode_for(name) == "reference":
        pol.bump("ref_calls")
        return True
    pol.bump("pallas_calls")
    return False


def _interp(name: str) -> bool:
    return current_policy().interpret_for(name)


# ----------------------------------------------------------------------------
# Kernel descriptor table — one record per public kernel
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OpDescriptor:
    """A kernel's public contract in one place.

    `shapes(*operands)` maps the wrapper's runtime operands to the
    pipeline-layer shape dict (the autotuner key); `operands(shapes,
    dtype)` is its inverse — synthetic random operands for a shape dict,
    which is what the autotuner's timed race runs candidates on (the real
    operands at a tuned_call miss may be jit tracers); `reference` is the
    pure-jnp composition the "reference" policy mode routes to (and the
    custom-VJP backward recomputes through, for fused kernels);
    `streamed_operand` is the index of the main streamed operand — the one
    whose dtype sets the VMEM tile footprint (weights/scales/alpha ride
    along). `fused` marks kernels whose Traffic carries `saved_bytes` (an
    eliminated intermediate); `composition` is the *unfused route* for a
    fused kernel — the same math built from the primitive Pallas wrappers
    plus jnp epilogues (NOT the pure-jnp `reference`) — which the timed
    race runs as one extra lane, so a fusion that loses to its own parts
    on real shapes is demoted per (kernel, shape) cell.
    """

    name: str
    wrapper: Callable
    shapes: Callable[..., dict]
    reference: Callable | None = None
    streamed_operand: int = 0
    fused: bool = False
    operands: Callable[[dict, Any], tuple] | None = None
    composition: Callable | None = None


OPS: dict[str, OpDescriptor] = {}


def register_op(desc: OpDescriptor) -> OpDescriptor:
    OPS[desc.name] = desc
    return desc


def wrapper_for(name: str):
    """Public name -> policy-dispatched wrapper (same table tuned_call uses)."""
    return OPS[name].wrapper


def kernel_shapes(name: str, *operands) -> dict:
    """The pipeline-layer shape dict for a kernel's runtime operands.

    Operand order matches the public wrapper, so `kernel_shapes(name,
    *args)` pairs with `tuned_call(name, *args)`.
    """
    return OPS[name].shapes(*operands)


def tuned_call(name: str, *operands, **kwargs):
    """Run a kernel under the active KernelPolicy: reference short-circuit,
    per-op block override, or autotuned (registry-cached) block sizes with
    autotune-on-miss — see `KernelPolicy.call`."""
    return current_policy().call(name, *operands, **kwargs)


# ----------------------------------------------------------------------------
# The unfused kernel suite
# ----------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _matmul_c(a, b, *, bm, bn, bk, interpret):
    return _matmul.matmul(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret)


def matmul(a, b, *, bm: int | None = None, bn: int | None = None,
           bk: int | None = None):
    if _take_reference("matmul"):
        return _ref.matmul(a, b)
    return _matmul_c(a, b, bm=bm, bn=bn, bk=bk, interpret=_interp("matmul"))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _axpy_c(alpha, x, y, *, block_rows, interpret):
    return _axpy.axpy(alpha, x, y, block_rows=block_rows, interpret=interpret)


def axpy(alpha, x, y, *, block_rows: int | None = None):
    if _take_reference("axpy"):
        return _ref.axpy(alpha, x, y)
    return _axpy_c(alpha, x, y, block_rows=block_rows,
                   interpret=_interp("axpy"))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _dotp_c(x, y, *, block_rows, interpret):
    return _dotp.dotp(x, y, block_rows=block_rows, interpret=interpret)


def dotp(x, y, *, block_rows: int | None = None):
    if _take_reference("dotp"):
        return _ref.dotp(x, y)
    return _dotp_c(x, y, block_rows=block_rows, interpret=_interp("dotp"))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _conv2d_c(x, w, *, block_rows, interpret):
    return _conv2d.conv2d_3x3(x, w, block_rows=block_rows,
                              interpret=interpret)


def conv2d_3x3(x, w, *, block_rows: int | None = None):
    if _take_reference("conv2d"):
        return _ref.conv2d_3x3(x, w)
    return _conv2d_c(x, w, block_rows=block_rows, interpret=_interp("conv2d"))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _dct8x8_c(blocks, *, block_n, interpret):
    return _dct8x8.dct8x8(blocks, block_n=block_n, interpret=interpret)


def dct8x8(blocks, *, block_n: int | None = None):
    if _take_reference("dct8x8"):
        return _ref.dct8x8(blocks)
    return _dct8x8_c(blocks, block_n=block_n, interpret=_interp("dct8x8"))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _rmsnorm_c(x, scale, *, block_rows, interpret):
    return _rmsnorm.rmsnorm(x, scale, block_rows=block_rows,
                            interpret=interpret)


def rmsnorm(x, scale, *, block_rows: int | None = None):
    if _take_reference("rmsnorm"):
        return _ref.rmsnorm(x, scale)
    return _rmsnorm_c(x, scale, block_rows=block_rows,
                      interpret=_interp("rmsnorm"))


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def _flash_attention_c(q, k, v, *, causal, bq, bk, interpret):
    return _fa.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk,
                               interpret=interpret)


def _ref_flash_attention(q, k, v, *, causal: bool = True, **_):
    g = q.shape[1] // k.shape[1]
    return _ref.flash_attention(q, jnp.repeat(k, g, axis=1),
                                jnp.repeat(v, g, axis=1), causal=causal)


def flash_attention(q, k, v, *, causal: bool = True, bq: int | None = None,
                    bk: int | None = None):
    if _take_reference("flash_attention"):
        return _ref_flash_attention(q, k, v, causal=causal)
    return _flash_attention_c(q, k, v, causal=causal, bq=bq, bk=bk,
                              interpret=_interp("flash_attention"))


def _ref_paged_decode_attention(q, k_pool, v_pool, lengths, pages, **_):
    from repro.models import attention as attn
    return attn.decode_attention(q, attn.paged_gather(k_pool, pages),
                                 attn.paged_gather(v_pool, pages), lengths,
                                 n_kv=k_pool.shape[2])


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def _paged_decode_attention_c(q, k_pool, v_pool, lengths, pages, *,
                              pages_per_block, interpret):
    return _pa.paged_decode_attention(q, k_pool, v_pool, lengths, pages,
                                      pages_per_block=pages_per_block,
                                      interpret=interpret)


def paged_decode_attention(q, k_pool, v_pool, lengths, pages, *,
                           pages_per_block: int | None = None):
    """One decode token per slot against the shared page pool, reading
    only the pages below each slot's length; the reference gathers each
    slot's whole page list and masks it."""
    if _take_reference("paged_decode_attention"):
        return _ref_paged_decode_attention(q, k_pool, v_pool, lengths, pages)
    return _paged_decode_attention_c(
        q, k_pool, v_pool, lengths, pages, pages_per_block=pages_per_block,
        interpret=_interp("paged_decode_attention"))


# ----------------------------------------------------------------------------
# Fused kernels: Pallas forward, reference-composition backward
# ----------------------------------------------------------------------------


def _ref_rmsnorm_matmul(x, scale, w, **_):
    return jnp.dot(_ref.rmsnorm(x, scale), w,
                   preferred_element_type=jnp.float32).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _rmsnorm_matmul_p(blocks: tuple, interpret: bool, x, scale, w):
    return _fused.rmsnorm_matmul(x, scale, w, interpret=interpret,
                                 **dict(blocks))


def _rmsnorm_matmul_fwd(blocks, interpret, x, scale, w):
    return _rmsnorm_matmul_p(blocks, interpret, x, scale, w), (x, scale, w)


def _rmsnorm_matmul_bwd(blocks, interpret, res, g):
    _, vjp = jax.vjp(_ref_rmsnorm_matmul, *res)
    return vjp(g)


_rmsnorm_matmul_p.defvjp(_rmsnorm_matmul_fwd, _rmsnorm_matmul_bwd)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def _rmsnorm_matmul_c(x, scale, w, *, bm, bn, interpret):
    return _rmsnorm_matmul_p((("bm", bm), ("bn", bn)), interpret, x, scale, w)


def rmsnorm_matmul(x, scale, w, *, bm: int | None = None,
                   bn: int | None = None):
    """matmul(rmsnorm(x, scale), w); the normed x never round-trips HBM."""
    if _take_reference("rmsnorm_matmul"):
        return _ref_rmsnorm_matmul(x, scale, w)
    return _rmsnorm_matmul_c(x, scale, w, bm=bm, bn=bn,
                             interpret=_interp("rmsnorm_matmul"))


def _ref_matmul_bias_act(act: str, a, b, bias):
    h = jnp.dot(a, b, preferred_element_type=jnp.float32) \
        + bias.astype(jnp.float32)
    return _fused.ACTIVATIONS[act](h).astype(a.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _matmul_bias_act_p(act: str, blocks: tuple, interpret: bool, a, b, bias):
    return _fused.matmul_bias_act(a, b, bias, act=act, interpret=interpret,
                                  **dict(blocks))


def _matmul_bias_act_fwd(act, blocks, interpret, a, b, bias):
    return _matmul_bias_act_p(act, blocks, interpret, a, b, bias), (a, b, bias)


def _matmul_bias_act_bwd(act, blocks, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(_ref_matmul_bias_act, act), *res)
    return vjp(g)


_matmul_bias_act_p.defvjp(_matmul_bias_act_fwd, _matmul_bias_act_bwd)


@functools.partial(jax.jit,
                   static_argnames=("act", "bm", "bn", "bk", "interpret"))
def _matmul_bias_act_c(a, b, bias, *, act, bm, bn, bk, interpret):
    return _matmul_bias_act_p(act, (("bm", bm), ("bn", bn), ("bk", bk)),
                              interpret, a, b, bias)


def matmul_bias_act(a, b, bias, *, act: str = "gelu", bm: int | None = None,
                    bn: int | None = None, bk: int | None = None):
    """act(a @ b + bias) with the epilogue applied before writeback."""
    if _take_reference("matmul_bias_act"):
        return _ref_matmul_bias_act(act, a, b, bias)
    return _matmul_bias_act_c(a, b, bias, act=act, bm=bm, bn=bn, bk=bk,
                              interpret=_interp("matmul_bias_act"))


def _ref_matmul_residual_add(a, b, res, **_):
    return (jnp.dot(a, b, preferred_element_type=jnp.float32)
            + res.astype(jnp.float32)).astype(a.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _matmul_residual_add_p(blocks: tuple, interpret: bool, a, b, res):
    return _fused.matmul_residual_add(a, b, res, interpret=interpret,
                                      **dict(blocks))


def _matmul_residual_add_fwd(blocks, interpret, a, b, res):
    return _matmul_residual_add_p(blocks, interpret, a, b, res), (a, b, res)


def _matmul_residual_add_bwd(blocks, interpret, res_, g):
    _, vjp = jax.vjp(_ref_matmul_residual_add, *res_)
    return vjp(g)


_matmul_residual_add_p.defvjp(_matmul_residual_add_fwd,
                              _matmul_residual_add_bwd)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _matmul_residual_add_c(a, b, res, *, bm, bn, bk, interpret):
    return _matmul_residual_add_p((("bm", bm), ("bn", bn), ("bk", bk)),
                                  interpret, a, b, res)


def matmul_residual_add(a, b, res, *, bm: int | None = None,
                        bn: int | None = None, bk: int | None = None):
    """a @ b + res; the matmul output never round-trips HBM."""
    if _take_reference("matmul_residual_add"):
        return _ref_matmul_residual_add(a, b, res)
    return _matmul_residual_add_c(a, b, res, bm=bm, bn=bn, bk=bk,
                                  interpret=_interp("matmul_residual_add"))


def _ref_flash_attention_proj(causal: bool, q, k, v, wo):
    g = q.shape[1] // k.shape[1]
    o = _ref.flash_attention(q, jnp.repeat(k, g, axis=1),
                             jnp.repeat(v, g, axis=1), causal=causal)
    return jnp.einsum("bhsk,hkd->bsd", o, wo).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _flash_attention_proj_p(causal: bool, blocks: tuple, interpret: bool,
                            q, k, v, wo):
    return _fused.flash_attention_proj(q, k, v, wo, causal=causal,
                                       interpret=interpret, **dict(blocks))


def _flash_attention_proj_fwd(causal, blocks, interpret, q, k, v, wo):
    return (_flash_attention_proj_p(causal, blocks, interpret, q, k, v, wo),
            (q, k, v, wo))


def _flash_attention_proj_bwd(causal, blocks, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(_ref_flash_attention_proj, causal),
                     *res)
    return vjp(g)


_flash_attention_proj_p.defvjp(_flash_attention_proj_fwd,
                               _flash_attention_proj_bwd)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def _flash_attention_proj_c(q, k, v, wo, *, causal, bq, bk, interpret):
    return _flash_attention_proj_p(causal, (("bq", bq), ("bk", bk)),
                                   interpret, q, k, v, wo)


def flash_attention_proj(q, k, v, wo, *, causal: bool = True,
                         bq: int | None = None, bk: int | None = None):
    """Flash attention with the output projection fused across heads."""
    if _take_reference("flash_attention_proj"):
        return _ref_flash_attention_proj(causal, q, k, v, wo)
    return _flash_attention_proj_c(q, k, v, wo, causal=causal, bq=bq, bk=bk,
                                   interpret=_interp("flash_attention_proj"))


# ----------------------------------------------------------------------------
# Descriptor registration
# ----------------------------------------------------------------------------


def _shapes_axpy(alpha, x, y):
    return {"m": x.shape[0], "n": x.shape[1]}


def _shapes_dotp(x, y):
    return {"m": x.shape[0], "n": x.shape[1]}


def _shapes_matmul(a, b):
    return {"m": a.shape[0], "k": a.shape[1], "n": b.shape[1]}


def _shapes_conv2d(x, w):
    return {"h": x.shape[0], "w": x.shape[1]}


def _shapes_dct8x8(blocks):
    return {"n": blocks.shape[0]}


def _shapes_rmsnorm(x, scale):
    return {"m": x.shape[0], "d": x.shape[1]}


def _shapes_flash_attention(q, k, v):
    b, h, s, hd = q.shape
    return {"b": b, "h": h, "kv": k.shape[1], "s": s, "hd": hd}


def _shapes_paged_decode_attention(q, k_pool, v_pool, lengths, pages):
    b, _, h, hd = q.shape
    _, ps, kv, _ = k_pool.shape
    return {"b": b, "h": h, "kv": kv, "hd": hd, "ps": ps,
            "npp": pages.shape[1]}


def _shapes_rmsnorm_matmul(x, scale, w):
    return {"m": x.shape[0], "k": x.shape[1], "n": w.shape[1]}


def _shapes_matmul_epilogue(a, b, extra):
    return {"m": a.shape[0], "k": a.shape[1], "n": b.shape[1]}


def _shapes_flash_attention_proj(q, k, v, wo):
    b, h, s, hd = q.shape
    return {"b": b, "h": h, "kv": k.shape[1], "s": s, "hd": hd,
            "dm": wo.shape[-1]}


# -- operand factories (the race's synthetic inputs, one per kernel) ---------


def _rand(seed: int, shape: tuple, dtype):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32) \
        .astype(dtype)


def _mk_axpy(s, dt):
    return (2.0, _rand(0, (s["m"], s["n"]), dt), _rand(1, (s["m"], s["n"]), dt))


def _mk_dotp(s, dt):
    return (_rand(2, (s["m"], s["n"]), dt), _rand(3, (s["m"], s["n"]), dt))


def _mk_matmul(s, dt):
    return (_rand(4, (s["m"], s["k"]), dt), _rand(5, (s["k"], s["n"]), dt))


def _mk_conv2d(s, dt):
    return (_rand(6, (s["h"], s["w"]), dt), _rand(7, (3, 3), dt))


def _mk_dct8x8(s, dt):
    return (_rand(8, (s["n"], 8, 8), dt),)


def _mk_rmsnorm(s, dt):
    return (_rand(9, (s["m"], s["d"]), dt),
            _rand(10, (s["d"],), dt) * jnp.asarray(0.1, dt))


def _mk_flash_attention(s, dt):
    b, h, kv, sq, hd = (s[k] for k in ("b", "h", "kv", "s", "hd"))
    return (_rand(11, (b, h, sq, hd), dt), _rand(12, (b, kv, sq, hd), dt),
            _rand(13, (b, kv, sq, hd), dt))


def _mk_paged_decode_attention(s, dt):
    b, h, kv, hd, ps, npp = (s[k] for k in ("b", "h", "kv", "hd", "ps",
                                              "npp"))
    pages = 1 + jnp.arange(b * npp, dtype=jnp.int32).reshape(b, npp)
    pool = (1 + b * npp, ps, kv, hd)
    return (_rand(27, (b, 1, h, hd), dt), _rand(28, pool, dt),
            _rand(29, pool, dt), jnp.full((b,), npp * ps, jnp.int32), pages)


def _mk_rmsnorm_matmul(s, dt):
    return (_rand(14, (s["m"], s["k"]), dt),
            _rand(15, (s["k"],), dt) * jnp.asarray(0.1, dt),
            _rand(16, (s["k"], s["n"]), dt))


def _mk_matmul_bias_act(s, dt):
    return (_rand(17, (s["m"], s["k"]), dt), _rand(18, (s["k"], s["n"]), dt),
            _rand(19, (s["n"],), dt))


def _mk_matmul_residual_add(s, dt):
    return (_rand(20, (s["m"], s["k"]), dt), _rand(21, (s["k"], s["n"]), dt),
            _rand(22, (s["m"], s["n"]), dt))


def _mk_flash_attention_proj(s, dt):
    b, h, kv, sq, hd, dm = (s[k] for k in ("b", "h", "kv", "s", "hd", "dm"))
    return (_rand(23, (b, h, sq, hd), dt), _rand(24, (b, kv, sq, hd), dt),
            _rand(25, (b, kv, sq, hd), dt),
            _rand(26, (h, hd, dm), dt) * jnp.asarray(0.1, dt))


def _ref_axpy(alpha, x, y, **_):
    return _ref.axpy(alpha, x, y)


def _ref_dotp(x, y, **_):
    return _ref.dotp(x, y)


def _ref_matmul(a, b, **_):
    return _ref.matmul(a, b)


def _ref_conv2d(x, w, **_):
    return _ref.conv2d_3x3(x, w)


def _ref_dct8x8(blocks, **_):
    return _ref.dct8x8(blocks)


def _ref_rmsnorm(x, scale, **_):
    return _ref.rmsnorm(x, scale)


def _ref_matmul_bias_act_op(a, b, bias, *, act: str = "gelu", **_):
    return _ref_matmul_bias_act(act, a, b, bias)


def _ref_flash_attention_proj_op(q, k, v, wo, *, causal: bool = True, **_):
    return _ref_flash_attention_proj(causal, q, k, v, wo)


# -- unfused compositions (the fused kernels' race opponents) ----------------
# Same math as the fused kernel but built from the primitive Pallas
# wrappers with jnp epilogues — i.e. what a caller would write without the
# fusion. Block kwargs are swallowed (`**_`): each primitive tunes itself
# through its own registry cell when called via the policy-dispatched
# wrappers, so the composition lane carries no blocking of its own.


def _comp_rmsnorm_matmul(x, scale, w, **_):
    return matmul(rmsnorm(x, scale), w)


def _comp_matmul_bias_act(a, b, bias, *, act: str = "gelu", **_):
    h = matmul(a, b).astype(jnp.float32) + bias.astype(jnp.float32)
    return _fused.ACTIVATIONS[act](h).astype(a.dtype)


def _comp_matmul_residual_add(a, b, res, **_):
    return (matmul(a, b).astype(jnp.float32)
            + res.astype(jnp.float32)).astype(a.dtype)


def _comp_flash_attention_proj(q, k, v, wo, *, causal: bool = True, **_):
    o = flash_attention(q, k, v, causal=causal)
    return jnp.einsum("bhsk,hkd->bsd", o, wo).astype(q.dtype)


for _desc in (
    OpDescriptor("axpy", axpy, _shapes_axpy, _ref_axpy, streamed_operand=1,
                 operands=_mk_axpy),
    OpDescriptor("dotp", dotp, _shapes_dotp, _ref_dotp, operands=_mk_dotp),
    OpDescriptor("matmul", matmul, _shapes_matmul, _ref_matmul,
                 operands=_mk_matmul),
    OpDescriptor("conv2d", conv2d_3x3, _shapes_conv2d, _ref_conv2d,
                 operands=_mk_conv2d),
    OpDescriptor("dct8x8", dct8x8, _shapes_dct8x8, _ref_dct8x8,
                 operands=_mk_dct8x8),
    OpDescriptor("rmsnorm", rmsnorm, _shapes_rmsnorm, _ref_rmsnorm,
                 operands=_mk_rmsnorm),
    OpDescriptor("flash_attention", flash_attention, _shapes_flash_attention,
                 _ref_flash_attention, operands=_mk_flash_attention),
    OpDescriptor("paged_decode_attention", paged_decode_attention,
                 _shapes_paged_decode_attention, _ref_paged_decode_attention,
                 operands=_mk_paged_decode_attention),
    OpDescriptor("rmsnorm_matmul", rmsnorm_matmul, _shapes_rmsnorm_matmul,
                 _ref_rmsnorm_matmul, fused=True,
                 operands=_mk_rmsnorm_matmul,
                 composition=_comp_rmsnorm_matmul),
    OpDescriptor("matmul_bias_act", matmul_bias_act, _shapes_matmul_epilogue,
                 _ref_matmul_bias_act_op, fused=True,
                 operands=_mk_matmul_bias_act,
                 composition=_comp_matmul_bias_act),
    OpDescriptor("matmul_residual_add", matmul_residual_add,
                 _shapes_matmul_epilogue, _ref_matmul_residual_add,
                 fused=True, operands=_mk_matmul_residual_add,
                 composition=_comp_matmul_residual_add),
    OpDescriptor("flash_attention_proj", flash_attention_proj,
                 _shapes_flash_attention_proj, _ref_flash_attention_proj_op,
                 fused=True, operands=_mk_flash_attention_proj,
                 composition=_comp_flash_attention_proj),
):
    register_op(_desc)
