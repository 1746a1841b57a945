"""Program-cell assembly: (arch x shape) -> (fn, abstract args, shardings).

Shared by the Cluster programs (cluster/session.py) and the multi-pod
dry-run CLI (launch/dryrun.py). Lives here — not in launch/dryrun — because
importing dryrun has a deliberate import-time side effect (forcing the XLA
host device count before jax initializes) that library code must not pay.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding

from repro.configs import input_specs
from repro.core import addressing
from repro.models import steps


def batch_logical(cfg, shape) -> dict:
    log = {"tokens": ("batch", "seq")}
    if shape.kind == "train":
        log["labels"] = ("batch", "seq")
    if shape.kind == "decode":
        log["tokens"] = ("batch", None)
        log["pos"] = ()
    if cfg.family == "encdec":
        log["enc_embeds"] = ("batch", None, None)
    if cfg.family == "vlm":
        log["img_embeds"] = ("batch", None, None)
    return log


def shardings_for(tree_sds, tree_logical, mesh, rules):
    def one(sds, logical):
        spec = rules.spec_for(logical, sds.shape, mesh)
        return NamedSharding(mesh, spec)
    return jax.tree.map(
        one, tree_sds, tree_logical,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def layer_gather_specs(cfg, mesh, rules):
    """PartitionSpecs for ONE super-block's weights with the `data` axis
    removed — forcing FSDP all-gathers inside the scan (variant fsdpgather)."""
    gather_rules = addressing.default_rules(mesh, fsdp=False,
                                            overrides=cfg.rules_overrides)
    p_sds, p_log = steps.abstract_params(cfg)

    def one(sds, logical):
        # strip the leading stacked "layers" dim
        return gather_rules.spec_for(logical[1:], sds.shape[1:], mesh)

    return jax.tree.map(
        one, p_sds["blocks"], p_log["blocks"],
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def group_devices(mesh, n_groups: int) -> tuple:
    """Slice a mesh's devices into per-group assignments (tiles -> groups).

    The sharded serving session builds one session cell per group and
    pins each group's params/state to its device, so group g's decode
    chunks run concurrently with every other group's. Devices are taken
    in the mesh's data-axis order; with fewer devices than groups the
    assignment wraps round-robin — groups share a device (degraded but
    functional: the scheduler semantics are unchanged, only the compute
    overlap is lost), which is what single-device CPU smoke runs hit.
    """
    if n_groups < 1:
        raise ValueError(f"n_groups must be >= 1, got {n_groups}")
    try:
        import numpy as np
        devs = [d for d in np.asarray(mesh.devices).reshape(-1)]
    except AttributeError:
        devs = list(jax.devices())
    if not devs:
        devs = list(jax.devices())
    return tuple(devs[g % len(devs)] for g in range(n_groups))


def build_cell(cfg, shape, mesh, rules, fsdp_gather: bool = False,
               policy=None, decode_chunk: int = 1, session: bool = False,
               max_prompt: int = 8, paged: bool = False,
               page_size: int = 16):
    """Returns (fn, args_sds, in_shardings, out_shardings, donate).

    A decode shape lowers the single decode step. `session=True` (decode
    shapes) builds the continuous-batching session cell instead: the
    `decode_chunk`-step slot-scheduled chunk over the donated pool state
    (per-slot positions, prompt buffers, budgets — see
    `engine.session_chunk_fn`), mirroring what a compiled
    `ServeSessionProgram` steps between refills; `decode_chunk > 1`
    without it raises `ValueError`. `paged=True` (session shapes) lowers
    the shared-paged-KV variant of that cell: pageable K/V leaves become
    the global page pool and the state carries the per-slot page tables
    (`ServeSessionProgram(paged=True)`).
    """
    batch_sds = input_specs(cfg, shape)
    batch_log = batch_logical(cfg, shape)
    batch_sh = shardings_for(batch_sds, batch_log, mesh, rules)

    if shape.kind == "train":
        wsc = layer_gather_specs(cfg, mesh, rules) if fsdp_gather else None
        fn = steps.make_train_step(cfg, layer_wsc=wsc, policy=policy)
        state_sds, state_log = steps.abstract_train_state(cfg, shape.seq_len)
        state_sh = shardings_for(state_sds, state_log, mesh, rules)
        out_sh = (state_sh, None)
        return fn, (state_sds, batch_sds), (state_sh, batch_sh), out_sh, (0,)

    params_sds, params_log = steps.abstract_params(cfg, shape.seq_len)
    params_sh = shardings_for(params_sds, params_log, mesh, rules)

    if shape.kind == "prefill":
        fn = steps.make_prefill_step(cfg, policy=policy)
        tok_sh = NamedSharding(
            mesh, rules.spec_for(("batch",), (shape.global_batch,), mesh))
        return (fn, (params_sds, batch_sds), (params_sh, batch_sh),
                tok_sh, ())

    # decode
    cache_len = steps.decode_cache_len(cfg, shape.seq_len)
    cache_sds, cache_log = steps.abstract_cache(cfg, shape.global_batch,
                                                cache_len)
    cache_sh = shardings_for(cache_sds, cache_log, mesh, rules)
    tok_sh = NamedSharding(
        mesh, rules.spec_for(("batch", None), (shape.global_batch, 1), mesh))
    if session:
        from repro.runtime import engine

        step = steps.make_decode_step(cfg, max_seq=shape.seq_len,
                                      policy=policy)
        fn = engine.session_chunk_fn(step, decode_chunk)
        B = shape.global_batch
        pps = None
        if paged:
            # pageable K/V leaves move into the shared pool; the state
            # grows a (B, pages_per_slot) page-table row
            pps = -((shape.seq_len + 1) // -page_size)   # ceil
            cache_sds, cache_log = steps.abstract_paged_cache(
                cfg, B, cache_len, n_pages=B * pps + 1,
                page_size=page_size)
            cache_sh = shardings_for(cache_sds, cache_log, mesh, rules)
        # the pool-state spec is whatever init_session_state builds — one
        # source of truth, so engine-side field changes propagate here
        state_sds = jax.eval_shape(
            lambda c: engine.init_session_state(c, B, max_prompt,
                                                pages_per_slot=pps),
            cache_sds)
        slot_sh = NamedSharding(mesh, rules.spec_for(("batch",), (B,), mesh))
        buf_sh = lambda n: NamedSharding(
            mesh, rules.spec_for(("batch", None), (B, n), mesh))
        state_sh = {k: (cache_sh if k == "cache" else
                        buf_sh(1) if k == "tok" else
                        buf_sh(max_prompt) if k == "prompt_buf" else
                        buf_sh(pps) if k == "pages" else slot_sh)
                    for k in state_sds}
        scalar_sh = NamedSharding(mesh, jax.sharding.PartitionSpec())
        out_sh = (state_sh, buf_sh(decode_chunk), buf_sh(decode_chunk),
                  slot_sh, scalar_sh)
        return fn, (params_sds, state_sds), (params_sh, state_sh), out_sh, (1,)
    if decode_chunk > 1:
        raise ValueError(
            f"decode_chunk={decode_chunk} without session=True: no program "
            f"runs a K-step decode cell outside the session")
    fn = steps.make_decode_step(cfg, max_seq=shape.seq_len, policy=policy)
    return (fn, (params_sds, cache_sds, batch_sds),
            (params_sh, cache_sh, batch_sh), (cache_sh, tok_sh), (1,))


def model_flops(cfg, shape) -> dict:
    n = cfg.n_params()
    n_act = cfg.n_active_params()
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        mf = 6.0 * n_act * d
    elif shape.kind == "prefill":
        d = shape.global_batch * shape.seq_len
        mf = 2.0 * n_act * d
    else:
        d = shape.global_batch
        mf = 2.0 * n_act * d
    return {"n_params": n, "n_active_params": n_act, "tokens": d,
            "model_flops": mf}
