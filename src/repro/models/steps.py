"""Step factories: compose blocks into train / prefill / decode programs.

Layers are organized as *super-blocks* (one period of cfg.pattern) and
scanned with `lax.scan` + remat, so the lowered HLO stays compact enough to
compile for 512 chips and the per-iteration weight all-gather is exposed for
latency hiding (the Snitch outstanding-load analogue — see core/overlap.py).

Layer layout: n_super complete periods (scanned, weights stacked on a
leading "layers" axis) followed by `n_layers % period` remainder layers
(unscanned). The cross-entropy is computed in sequence chunks with remat so
(B, S, vocab) logits never materialize.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.cluster import policy as kpolicy
from repro.core import overlap
from repro.models.blocks import BLOCKS
from repro.models.layers import (ParamSpec, abstract_tree, init_tree,
                                 logical_tree, layer_norm, rms_norm)
from repro.optim import AdamConfig, adam_init, adam_update, warmup_cosine

F32 = jnp.float32

AUX_COEF = 1e-2     # MoE load-balance loss weight
Z_COEF = 1e-4       # z-loss weight
LOSS_CHUNK = 512    # sequence chunk for the fused CE


# ----------------------------------------------------------------------------
# Layer plan
# ----------------------------------------------------------------------------

def block_plan(cfg) -> tuple[tuple[str, ...], int, tuple[str, ...]]:
    if cfg.family == "vlm" and cfg.cross_every:
        pattern = ("attn",) * (cfg.cross_every - 1) + ("cross",)
    else:
        pattern = cfg.pattern
    period = len(pattern)
    return pattern, cfg.n_layers // period, pattern[: cfg.n_layers % period]


def _stack(specs, n: int):
    def one(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n, *s.shape), ("layers", *s.logical), s.dtype,
                         s.init, s.scale)
    return jax.tree.map(one, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


# ----------------------------------------------------------------------------
# Parameter specs
# ----------------------------------------------------------------------------

def param_specs(cfg, max_seq: int = 4096) -> dict:
    pattern, n_super, remainder = block_plan(cfg)
    d = cfg.d_model
    specs: dict[str, Any] = {
        "tok_embed": ParamSpec((cfg.vocab, d), ("vocab", None), init="embed",
                               scale=1.0),
        "unembed": ParamSpec((d, cfg.vocab), ("embed", "vocab")),
    }
    if cfg.norm == "rms":
        specs["ln_f"] = ParamSpec((d,), ("norm",), init="zeros")
    else:
        specs["ln_f_s"] = ParamSpec((d,), ("norm",), init="ones")
        specs["ln_f_b"] = ParamSpec((d,), ("norm",), init="zeros")
    specs["blocks"] = {
        f"sub{i}": _stack(BLOCKS[k]["specs"](cfg), n_super)
        for i, k in enumerate(pattern)}
    if remainder:
        specs["rem"] = {f"rem{i}": BLOCKS[k]["specs"](cfg)
                        for i, k in enumerate(remainder)}
    if cfg.family == "encdec":
        specs["enc"] = {
            "blocks": _stack(BLOCKS["enc_attn"]["specs"](cfg), cfg.n_enc_layers),
            "pos": ParamSpec((cfg.enc_seq, d), (None, None), init="embed",
                             scale=0.02),
            "ln_s": ParamSpec((d,), ("norm",), init="ones"),
            "ln_b": ParamSpec((d,), ("norm",), init="zeros"),
        }
        specs["dec_pos"] = ParamSpec((max_seq, d), (None, None), init="embed",
                                     scale=0.02)
    return specs


def abstract_params(cfg, max_seq: int = 4096):
    specs = param_specs(cfg, max_seq)
    return abstract_tree(specs), logical_tree(specs)


def init_params(cfg, key, max_seq: int = 4096):
    return init_tree(param_specs(cfg, max_seq), key)


# ----------------------------------------------------------------------------
# Decode cache specs
# ----------------------------------------------------------------------------

def cache_specs(cfg, B: int, cache_len: int) -> dict:
    pattern, n_super, remainder = block_plan(cfg)
    specs: dict[str, Any] = {"blocks": {
        f"sub{i}": _stack(BLOCKS[k]["cache"](cfg, B, cache_len), n_super)
        for i, k in enumerate(pattern)}}
    if remainder:
        specs["rem"] = {f"rem{i}": BLOCKS[k]["cache"](cfg, B, cache_len)
                        for i, k in enumerate(remainder)}
    return specs


def abstract_cache(cfg, B: int, cache_len: int):
    specs = cache_specs(cfg, B, cache_len)
    return abstract_tree(specs), logical_tree(specs)


def init_cache(cfg, B: int, cache_len: int):
    return init_tree(cache_specs(cfg, B, cache_len), jax.random.PRNGKey(0))


def _map_cache_axes(cache, fn_for_axis):
    """Apply `fn_for_axis(batch_axis)` leaf-wise over a decode cache.
    Stacked super-block leaves carry a leading `layers` axis, so the batch
    axis is 1 under `blocks` and 0 under `rem` — every per-slot cache
    operation (zero, fill, take, put, NaN scan) shares this layout fact."""
    out = {"blocks": jax.tree.map(fn_for_axis(1), cache["blocks"])}
    if "rem" in cache:
        out["rem"] = jax.tree.map(fn_for_axis(0), cache["rem"])
    return out


def fill_cache_slots(cache, mask, value):
    """Fill the per-slot decode state of masked batch rows with `value`.

    `mask` is (B,) bool. Non-float leaves are left untouched when `value`
    is not finite (NaN fault injection must not corrupt integer state)."""
    import math
    finite = math.isfinite(value)

    def at_axis(axis):
        def one(c):
            if not finite and not jnp.issubdtype(c.dtype, jnp.inexact):
                return c
            shape = [1] * c.ndim
            shape[axis] = mask.shape[0]
            return jnp.where(mask.reshape(shape),
                             jnp.asarray(value, c.dtype), c)
        return one

    return _map_cache_axes(cache, at_axis)


def zero_cache_slots(cache, mask):
    """Zero the per-slot decode state of masked batch rows.

    `mask` is (B,) bool. Needed when a slot is recycled for a new request:
    KV rows beyond the (reset) position are masked out by decode attention
    anyway, but recurrent block states (mLSTM/sLSTM/RG-LRU matrices, conv
    tails) carry the old request's activations and must be cleared.
    """
    return fill_cache_slots(cache, mask, 0.0)


def take_cache_slot(cache, slot):
    """Slice one slot's rows out of every cache leaf (the device half of a
    slot checkpoint — see `engine.make_slot_snapshot`)."""
    def at_axis(axis):
        return lambda c: jax.lax.dynamic_index_in_dim(c, slot, axis=axis,
                                                      keepdims=False)
    return _map_cache_axes(cache, at_axis)


def put_cache_slot(cache, slot, rows):
    """Write `rows` (a `take_cache_slot` result) back into slot `slot` —
    bit-exact, so a preempted request resumes identically."""
    def put(axis, c, r):
        idx = [slice(None)] * c.ndim
        idx[axis] = slot
        return c.at[tuple(idx)].set(r)

    out = {"blocks": jax.tree.map(lambda c, r: put(1, c, r),
                                  cache["blocks"], rows["blocks"])}
    if "rem" in cache:
        out["rem"] = jax.tree.map(lambda c, r: put(0, c, r),
                                  cache["rem"], rows["rem"])
    return out


def nan_cache_slots(cache):
    """(B,) bool: any-NaN per slot across every float cache leaf — the
    corruption sentinel `engine.make_nan_scan` compiles for the session."""
    flags = []

    def at_axis(axis):
        def one(c):
            if jnp.issubdtype(c.dtype, jnp.inexact):
                axes = tuple(i for i in range(c.ndim) if i != axis)
                flags.append(jnp.any(jnp.isnan(c), axis=axes))
            return c
        return one

    _map_cache_axes(cache, at_axis)
    out = flags[0]
    for f in flags[1:]:
        out = out | f
    return out


# ----------------------------------------------------------------------------
# Paged decode cache — the shared-pool layout (runtime/kvpool.py)
# ----------------------------------------------------------------------------
#
# Under a paged session the positional K/V leaves stop being per-slot
# rectangles (B, L, KV, hd) and become ONE shared pool (n_pages, page_size,
# KV, hd) addressed through per-slot page tables (`ctx["pages"]` in the
# decode step). Rolling-window buffers and recurrent block states are not
# pageable — their `pos % window` addressing / dense state is a layout of
# its own — so they stay private (B, ...) leaves; the two kinds coexist in
# one cache pytree (in one layer, for "attn_ssm") and every per-slot op
# below routes each leaf by a structural mask.

def _kind_paged(cfg, kind: str) -> bool:
    """Does this block kind route K/V through the pool? Mirrors the
    `_paged(ctx, window)` gate in blocks.py: positional attention pages,
    windowed attention stays a private rolling buffer."""
    if kind in ("attn", "attn_moe", "attn_ssm"):
        return not cfg.window
    return kind == "attn_cross"        # self_k/self_v (cross_* is static)


def recurrent_state(cfg) -> bool:
    """Does some layer keep state that only the request's whole token
    stream builds (a recurrence, a conv tail)? A slot's state then cannot
    start from shared prefix pages, so paged sessions reuse no prefix."""
    pattern, _, remainder = block_plan(cfg)
    return any(BLOCKS[k].get("recurrent", False)
               for k in pattern + remainder)


def _pageable_leaf(spec: ParamSpec) -> bool:
    return tuple(spec.logical[:2]) == ("batch", "kv_seq")


def _paged_kind_specs(cfg, kind: str, B: int, cache_len: int,
                      n_pages: int, page_size: int):
    specs = BLOCKS[kind]["cache"](cfg, B, cache_len)
    if not _kind_paged(cfg, kind):
        return specs

    def one(s: ParamSpec) -> ParamSpec:
        if not _pageable_leaf(s):
            return s
        return ParamSpec((n_pages, page_size, *s.shape[2:]),
                         (None, None, *s.logical[2:]), s.dtype, s.init,
                         s.scale)

    return jax.tree.map(one, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def paged_cache_specs(cfg, B: int, cache_len: int, *, n_pages: int,
                      page_size: int) -> dict:
    """`cache_specs` with every pageable K/V leaf replaced by the shared
    pool (n_pages, page_size, KV, hd); stacked super-blocks carry the
    usual leading layers axis, i.e. (n_super, n_pages, ps, KV, hd)."""
    pattern, n_super, remainder = block_plan(cfg)
    kinds = pattern + remainder
    if not any(_kind_paged(cfg, k) and any(
            _pageable_leaf(s) for s in jax.tree.leaves(
                BLOCKS[k]["cache"](cfg, B, cache_len),
                is_leaf=lambda x: isinstance(x, ParamSpec)))
            for k in kinds):
        raise ValueError(
            f"arch {cfg.name!r} has no pageable KV leaves (recurrent or "
            f"fully windowed) — paged serving needs positional attention")
    specs: dict[str, Any] = {"blocks": {
        f"sub{i}": _stack(_paged_kind_specs(cfg, k, B, cache_len,
                                            n_pages, page_size), n_super)
        for i, k in enumerate(pattern)}}
    if remainder:
        specs["rem"] = {f"rem{i}": _paged_kind_specs(cfg, k, B, cache_len,
                                                     n_pages, page_size)
                        for i, k in enumerate(remainder)}
    return specs


def paged_cache_mask(cfg, B: int, cache_len: int) -> dict:
    """Same tree structure as the cache, True on pool leaves — the routing
    fact every paged per-slot op shares."""
    pattern, n_super, remainder = block_plan(cfg)

    def mask_tree(kind: str):
        paged = _kind_paged(cfg, kind)
        return jax.tree.map(lambda s: paged and _pageable_leaf(s),
                            BLOCKS[kind]["cache"](cfg, B, cache_len),
                            is_leaf=lambda x: isinstance(x, ParamSpec))

    mask: dict[str, Any] = {"blocks": {f"sub{i}": mask_tree(k)
                                       for i, k in enumerate(pattern)}}
    if remainder:
        mask["rem"] = {f"rem{i}": mask_tree(k)
                       for i, k in enumerate(remainder)}
    return mask


def abstract_paged_cache(cfg, B: int, cache_len: int, *, n_pages: int,
                         page_size: int):
    specs = paged_cache_specs(cfg, B, cache_len, n_pages=n_pages,
                              page_size=page_size)
    return abstract_tree(specs), logical_tree(specs)


def init_paged_cache(cfg, B: int, cache_len: int, *, n_pages: int,
                     page_size: int):
    return init_tree(paged_cache_specs(cfg, B, cache_len, n_pages=n_pages,
                                       page_size=page_size),
                     jax.random.PRNGKey(0))


def make_paged_cache_ops(cfg, B: int, cache_len: int):
    """The per-slot / per-page device ops of a paged cache, routed by the
    pool mask. Returned as a dict of pure functions (the engine wrappers
    jit them):

    * ``zero_slots(cache, mask)`` — refill zeroing of *private* leaves
      only (recurrent/rolling state must not leak across requests);
      pool pages are deliberately NOT zeroed — stale page data is masked
      out by decode attention, which is the point of paged refill.
    * ``nan_slots(cache, tables)`` — (B,) any-NaN per slot; private
      leaves by batch row, pool leaves via the slot's page table
      (trash-page entries ignored so one poisoned slot cannot flag
      every retired neighbour).
    * ``corrupt_slots(cache, mask, tables)`` — NaN-fill masked slots:
      private float rows directly, pool pages via a scatter of the
      masked slots' table entries.
    * ``copy_pages(cache, src, dst)`` — pool page copy (the COW fork).
    * ``zero_pages(cache, pages)`` — pool page scrub (NaN quarantine).
    * ``read_pages(cache, pages)`` — gather the requested pool pages,
      page axis moved to the front of every returned array, so the host
      can checksum page content (integrity stamp/verify).
    * ``flip_pages(cache, pages)`` — *silent* corruption for the
      ``bit_flip`` fault: perturb the pages' float content by +1
      (finite values — the NaN sentinel scan cannot see it by design;
      only the content checksum catches it).
    """
    mask = paged_cache_mask(cfg, B, cache_len)

    def _map(cache, fn_for):
        out = {"blocks": jax.tree.map(lambda c, m: fn_for(1, m)(c),
                                      cache["blocks"], mask["blocks"])}
        if "rem" in cache:
            out["rem"] = jax.tree.map(lambda c, m: fn_for(0, m)(c),
                                      cache["rem"], mask["rem"])
        return out

    def zero_slots(cache, slot_mask):
        def fn_for(axis, paged):
            if paged:
                return lambda c: c
            def one(c):
                shape = [1] * c.ndim
                shape[axis] = slot_mask.shape[0]
                return jnp.where(slot_mask.reshape(shape),
                                 jnp.zeros((), c.dtype), c)
            return one
        return _map(cache, fn_for)

    def nan_slots(cache, tables):
        flags = []
        live = tables != 0                       # TRASH_PAGE entries

        def fn_for(axis, paged):
            def one(c):
                if not jnp.issubdtype(c.dtype, jnp.inexact):
                    return c
                if paged:
                    # page axis sits where the batch axis would (the
                    # layers axis, if any, still leads)
                    axes = tuple(i for i in range(c.ndim) if i != axis)
                    per_page = jnp.any(jnp.isnan(c), axis=axes)
                    flags.append(jnp.any(per_page[tables] & live, axis=1))
                else:
                    axes = tuple(i for i in range(c.ndim) if i != axis)
                    flags.append(jnp.any(jnp.isnan(c), axis=axes))
                return c
            return one

        _map(cache, fn_for)
        out = flags[0]
        for f in flags[1:]:
            out = out | f
        return out

    def corrupt_slots(cache, slot_mask, tables):
        import math
        n_hit = None

        def fn_for(axis, paged):
            def one(c):
                nonlocal n_hit
                if not jnp.issubdtype(c.dtype, jnp.inexact):
                    return c
                nan = jnp.asarray(float("nan"), c.dtype)
                if paged:
                    n_pages = c.shape[axis]
                    if n_hit is None or n_hit.shape[0] != n_pages:
                        hit0 = jnp.zeros((n_pages,), bool)
                        n_hit = hit0.at[tables].max(
                            slot_mask[:, None] & (tables != 0))
                    shape = [1] * c.ndim
                    shape[axis] = n_pages
                    return jnp.where(n_hit.reshape(shape), nan, c)
                shape = [1] * c.ndim
                shape[axis] = slot_mask.shape[0]
                return jnp.where(slot_mask.reshape(shape), nan, c)
            return one

        del math
        return _map(cache, fn_for)

    def copy_pages(cache, src, dst):
        s = jnp.asarray(src, jnp.int32)
        d = jnp.asarray(dst, jnp.int32)

        def fn_for(axis, paged):
            def one(c):
                if not paged:
                    return c
                if axis == 1:
                    return c.at[:, d].set(c[:, s])
                return c.at[d].set(c[s])
            return one
        return _map(cache, fn_for)

    def zero_pages(cache, pages):
        idx = jnp.asarray(pages, jnp.int32)

        def fn_for(axis, paged):
            def one(c):
                if not paged:
                    return c
                zero = jnp.zeros((), c.dtype)
                if axis == 1:
                    return c.at[:, idx].set(zero)
                return c.at[idx].set(zero)
            return one
        return _map(cache, fn_for)

    def read_pages(cache, pages):
        idx = jnp.asarray(pages, jnp.int32)
        out = []

        def fn_for(axis, paged):
            def one(c):
                if not paged:
                    return c
                if axis == 1:
                    # (layers, pages, ...) -> page-major (n, layers, ...)
                    out.append(jnp.moveaxis(c[:, idx], 1, 0))
                else:
                    out.append(c[idx])
                return c
            return one

        _map(cache, fn_for)
        return tuple(out)

    def flip_pages(cache, pages):
        idx = jnp.asarray(pages, jnp.int32)

        def fn_for(axis, paged):
            def one(c):
                if not paged or not jnp.issubdtype(c.dtype, jnp.inexact):
                    return c
                one_v = jnp.ones((), c.dtype)
                if axis == 1:
                    return c.at[:, idx].add(one_v)
                return c.at[idx].add(one_v)
            return one
        return _map(cache, fn_for)

    return {"zero_slots": zero_slots, "nan_slots": nan_slots,
            "corrupt_slots": corrupt_slots, "copy_pages": copy_pages,
            "zero_pages": zero_pages, "read_pages": read_pages,
            "flip_pages": flip_pages}


# ----------------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------------

def _final_norm(cfg, params, x):
    if cfg.norm == "rms":
        return rms_norm(x, params["ln_f"], cfg.norm_eps)
    return layer_norm(x, params["ln_f_s"], params["ln_f_b"])


def _embed(cfg, params, tokens):
    x = jnp.take(params["tok_embed"], tokens, axis=0)
    if cfg.embed_mult != 1.0:
        x = x * cfg.embed_mult
    return x


def _logits(cfg, unembed, h, spec: str):
    """The unembedding in float32, with the published LM-head multiplier."""
    out = jnp.einsum(spec, h, unembed, preferred_element_type=F32)
    if cfg.lm_head_mult != 1.0:
        out = out * cfg.lm_head_mult
    return out


def _encode(cfg, params, enc_embeds):
    """Whisper encoder over stub frame embeddings."""
    x = enc_embeds + params["enc"]["pos"].astype(enc_embeds.dtype)
    B, S = x.shape[:2]
    ctx = {"positions": jnp.broadcast_to(jnp.arange(S), (B, S)), "rope": False}

    def body(carry, layer_params):
        x, = carry
        x, _ = BLOCKS["enc_attn"]["apply"](cfg, layer_params, x, ctx)
        return (x,), None

    (x,), _ = overlap.prefetchable_scan(body, (x,), params["enc"]["blocks"],
                                        remat_policy=cfg.remat)
    return layer_norm(x, params["enc"]["ln_s"], params["enc"]["ln_b"])


def forward(cfg, params, tokens, *, cross_embeds=None, layer_wsc=None):
    """Token ids -> final hidden states (B, S, d) and aux loss.

    `layer_wsc`: optional PartitionSpec tree matching one super-block's
    params. When given, the scan body re-constrains the sliced layer weights
    to those specs — used to force true FSDP semantics (all-gather the
    layer's weights over `data` once per layer) where GSPMD would otherwise
    choose partial-sum all-reduces of activation-sized buffers per einsum
    (see EXPERIMENTS.md §Perf H2).
    """
    pattern, n_super, remainder = block_plan(cfg)
    B, S = tokens.shape
    x = _embed(cfg, params, tokens)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    if cfg.family == "encdec":
        cross_embeds = _encode(cfg, params, cross_embeds)
        x = x + params["dec_pos"][:S].astype(x.dtype)
    ctx = {"positions": positions, "rope": cfg.family != "encdec",
           "cross_embeds": cross_embeds, "max_seq": S}

    def super_body(carry, super_params):
        x, aux = carry
        if layer_wsc is not None:
            super_params = jax.tree.map(overlap.with_sharding, super_params,
                                        layer_wsc)
        for i, kind in enumerate(pattern):
            x, a = BLOCKS[kind]["apply"](cfg, super_params[f"sub{i}"], x, ctx)
            aux = aux + a
        return (x, aux), None

    (x, aux), _ = overlap.prefetchable_scan(
        super_body, (x, jnp.zeros((), F32)), params["blocks"],
        remat_policy=cfg.remat)
    for i, kind in enumerate(remainder):
        x, a = BLOCKS[kind]["apply"](cfg, params["rem"][f"rem{i}"], x, ctx)
        aux = aux + a
    return _final_norm(cfg, params, x), aux


# ----------------------------------------------------------------------------
# Loss (chunked over sequence; logits never materialize at (B, S, V))
# ----------------------------------------------------------------------------

def _chunked_ce(cfg, unembed, hidden, labels):
    B, S, d = hidden.shape
    c = min(LOSS_CHUNK, S)
    if S % c:
        c = S
    nc = S // c
    split = lambda a: jnp.moveaxis(a.reshape(B, nc, c, *a.shape[2:]), 1, 0)

    @jax.checkpoint
    def body(carry, blk):
        h, y = blk
        logits = _logits(cfg, unembed, h, "bcd,dv->bcv")
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(y, cfg.vocab, dtype=F32)
        ll = jnp.sum(logits * onehot, axis=-1)
        nll = (lse - ll).sum()
        z = Z_COEF * jnp.square(lse).sum()
        return carry + nll + z, None

    total, _ = jax.lax.scan(body, jnp.zeros((), F32),
                            (split(hidden), split(labels)))
    return total / (B * S)


def loss_fn(cfg, params, batch, layer_wsc=None):
    cross = batch.get("enc_embeds", batch.get("img_embeds"))
    hidden, aux = forward(cfg, params, batch["tokens"], cross_embeds=cross,
                          layer_wsc=layer_wsc)
    ce = _chunked_ce(cfg, params["unembed"], hidden, batch["labels"])
    return ce + AUX_COEF * aux, {"ce": ce, "aux": aux}


# ----------------------------------------------------------------------------
# Train step
# ----------------------------------------------------------------------------

def make_train_step(cfg, *, adam: AdamConfig | None = None,
                    schedule_kwargs: dict | None = None, layer_wsc=None,
                    policy=None):
    """`policy` (KernelPolicy | mode string | None) pins the kernel policy
    the step traces under; None inherits the ambient scope *at trace time*
    — the policy is baked into the jit trace, so re-scoping the ambient
    policy around an already-jitted step does not re-route it. Build one
    step per policy (as Cluster.compile does) to compare routes."""
    pol = kpolicy.as_policy(policy) if policy is not None else None
    adam = adam or AdamConfig(moment_dtype=cfg.moment_dtype)
    sched = functools.partial(warmup_cosine, **(schedule_kwargs or {}))
    acc_dtype = jnp.bfloat16 if cfg.moment_dtype == "bfloat16" else F32

    def _body(state, batch):
        params = state["params"]
        k = cfg.grad_accum
        grad_fn = jax.value_and_grad(
            lambda p, mb: loss_fn(cfg, p, mb, layer_wsc), has_aux=True)
        if k <= 1:
            (loss, parts), grads = grad_fn(params, batch)
        else:
            micro = jax.tree.map(
                lambda a: a.reshape(k, a.shape[0] // k, *a.shape[1:]), batch)

            def step_i(carry, mb):
                gacc, lacc = carry
                (l, _), g = grad_fn(params, mb)
                gacc = jax.tree.map(
                    lambda a, b: a + b.astype(acc_dtype), gacc, g)
                return (gacc, lacc + l), None

            # p * 0 (not jnp.zeros) so the accumulator inherits each param's
            # sharding — a fresh zeros carry would let GSPMD pick replicated
            # layouts for the whole accumulation loop state.
            gacc0 = jax.tree.map(
                lambda p: (p * 0).astype(acc_dtype), params)
            (gacc, lsum), _ = jax.lax.scan(
                step_i, (gacc0, jnp.zeros((), F32)), micro)
            grads = jax.tree.map(lambda g: g / k, gacc)
            loss = lsum / k
            parts = {}
        lr_scale = sched(state["opt"]["step"] + 1)
        new_params, new_opt, om = adam_update(params, grads, state["opt"],
                                              adam, lr_scale)
        metrics = {"loss": loss, "lr_scale": lr_scale, **om}
        if parts:
            metrics |= parts
        return {"params": new_params, "opt": new_opt}, metrics

    def train_step(state, batch):
        with kpolicy.scoped(pol):
            return _body(state, batch)

    return train_step


def abstract_train_state(cfg, max_seq: int = 4096):
    """(state_sds, state_logical) for dry-run lowering and planning."""
    p_sds, p_log = abstract_params(cfg, max_seq)
    mdt = jnp.dtype(cfg.moment_dtype)
    m_sds = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, mdt), p_sds)
    state_sds = {"params": p_sds,
                 "opt": {"m": m_sds, "v": m_sds,
                         "step": jax.ShapeDtypeStruct((), jnp.int32)}}
    state_log = {"params": p_log,
                 "opt": {"m": p_log, "v": p_log, "step": ()}}
    return state_sds, state_log


def init_train_state(cfg, key, max_seq: int = 4096,
                     adam: AdamConfig | None = None):
    adam = adam or AdamConfig(moment_dtype=cfg.moment_dtype)
    params = init_params(cfg, key, max_seq)
    return {"params": params, "opt": adam_init(params, adam)}


# ----------------------------------------------------------------------------
# Prefill / decode steps
# ----------------------------------------------------------------------------

def make_prefill_step(cfg, *, policy=None):
    pol = kpolicy.as_policy(policy) if policy is not None else None

    def prefill_step(params, batch):
        with kpolicy.scoped(pol):
            cross = batch.get("enc_embeds", batch.get("img_embeds"))
            hidden, _ = forward(cfg, params, batch["tokens"],
                                cross_embeds=cross)
            logits = _logits(cfg, params["unembed"], hidden[:, -1],
                             "bd,dv->bv")
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    return prefill_step


def _paged_layer_scan(cfg, super_layer, x, blocks_params, blocks_cache, ctx):
    """The decode step's layer scan over a paged cache, with every cache
    leaf in the scan's carry.

    As scan xs/ys, a stacked leaf (n_super, ...) would be sliced out whole
    per layer and written back whole into a new stacked buffer: copies of
    the whole pool, and of every slot's recurrent state, each step. In the
    carry each leaf is addressed in place:

    * a pool leaf rides as one flat (n_super * n_pages, ps, KV, hd) view
      (merging the leading axes is a bitcast): layer l's pages are
      `pages + l * n_pages`, so the row scatter and the page gather reach
      the carried buffer; layer l's trash page `l * n_pages` lies inside
      its own region;
    * a private leaf (recurrent state, conv tails, windowed buffers, cross
      K/V) stays stacked: the block reads layer l's slice and its new
      slice is written back at l, which the TPU compiler fuses into the
      update's own reads and an in-place write. (A gather and scatter of
      the layer's rows would read the same on the CPU, but the TPU
      compiler lowers that gather by slicing the whole leaf.)"""
    leaves, treedef = jax.tree.flatten(blocks_cache)
    is_pool = treedef.flatten_up_to(paged_cache_mask(cfg, 1, 1)["blocks"])
    n_super = leaves[0].shape[0]
    n_pages = next(c.shape[1] for c, m in zip(leaves, is_pool) if m)

    def body(carry, scanned):
        x, cache = carry
        layer_params, layer = scanned
        layer_cache = [c if m else
                       jax.lax.dynamic_index_in_dim(c, layer, keepdims=False)
                       for c, m in zip(cache, is_pool)]
        layer_ctx = dict(ctx, pages=ctx["pages"] + layer * n_pages)
        x, new_cache = super_layer(x, layer_params,
                                   treedef.unflatten(layer_cache), layer_ctx)
        new = treedef.flatten_up_to(new_cache)
        cache = [n if m else jax.lax.dynamic_update_index_in_dim(c, n, layer, 0)
                 for c, n, m in zip(cache, new, is_pool)]
        return (x, cache), None

    cache = [c.reshape(n_super * c.shape[1], *c.shape[2:]) if m else c
             for c, m in zip(leaves, is_pool)]
    (x, cache), _ = jax.lax.scan(
        body, (x, cache),
        (blocks_params, jnp.arange(n_super, dtype=jnp.int32)))
    return x, treedef.unflatten([f.reshape(c.shape)
                                 for f, c in zip(cache, leaves)])


def make_decode_step(cfg, max_seq: int = 1 << 30, *, policy=None,
                     logits: bool = False):
    """`max_seq` is the workload's logical context length; caches shorter
    than it (windowed archs) operate as rolling buffers. `policy` pins the
    kernel policy the step traces under (None -> ambient). `logits=True`
    returns the (B, V) float32 logits in place of the greedy (B, 1) token.

    `batch["pos"]` is a scalar (all slots at the same position — the batch
    program) or a (B,) vector (per-slot positions — the continuous-batching
    session, where each slot is mid-way through its own request)."""
    pol = kpolicy.as_policy(policy) if policy is not None else None
    pattern, n_super, remainder = block_plan(cfg)

    def _body(params, cache, batch):
        tokens, pos = batch["tokens"], jnp.asarray(batch["pos"])
        B = tokens.shape[0]
        x = _embed(cfg, params, tokens)                         # (B,1,d)
        if cfg.family == "encdec":
            if pos.ndim == 0:
                dp = jax.lax.dynamic_slice_in_dim(params["dec_pos"], pos, 1,
                                                  axis=0)
            else:
                dp = jnp.take(params["dec_pos"], pos, axis=0)[:, None]
            x = x + dp.astype(x.dtype)
        if pos.ndim == 0:
            positions = jnp.broadcast_to(pos[None, None], (B, 1))
        else:
            positions = pos[:, None]
        positions = positions.astype(jnp.int32)
        ctx = {"positions": positions, "rope": cfg.family != "encdec",
               "max_seq": max_seq, "pages": batch.get("pages")}

        def super_layer(x, layer_params, layer_cache, layer_ctx):
            new_cache = {}
            for i, kind in enumerate(pattern):
                x, c = BLOCKS[kind]["decode"](cfg, layer_params[f"sub{i}"], x,
                                              layer_cache[f"sub{i}"], pos,
                                              layer_ctx)
                new_cache[f"sub{i}"] = c
            return x, new_cache

        if ctx["pages"] is None:
            x, new_blocks = jax.lax.scan(
                lambda x, lc: super_layer(x, *lc, ctx), x,
                (params["blocks"], cache["blocks"]))
        else:
            x, new_blocks = _paged_layer_scan(cfg, super_layer, x,
                                              params["blocks"],
                                              cache["blocks"], ctx)
        new_cache: dict[str, Any] = {"blocks": new_blocks}
        if remainder:
            new_cache["rem"] = {}
            for i, kind in enumerate(remainder):
                x, c = BLOCKS[kind]["decode"](
                    cfg, params["rem"][f"rem{i}"], x,
                    cache["rem"][f"rem{i}"], pos, ctx)
                new_cache["rem"][f"rem{i}"] = c
        with jax.named_scope("unembed"):
            x = _final_norm(cfg, params, x)
            out = _logits(cfg, params["unembed"], x, "bsd,dv->bsv")[:, 0]
        if logits:
            return new_cache, out
        return new_cache, jnp.argmax(out, axis=-1).astype(jnp.int32)[:, None]

    def decode_step(params, cache, batch):
        with kpolicy.scoped(pol):
            return _body(params, cache, batch)

    return decode_step


def decode_cache_len(cfg, seq_len: int) -> int:
    """Physical cache length: windowed archs keep a rolling window buffer."""
    if cfg.window and cfg.window < seq_len:
        return cfg.window
    return seq_len
