"""Shared paged KV pool (runtime/kvpool.py) — invariants + serving paths.

The pool is the software shared-L1: one global array of KV pages, slots
hold page tables, prefixes are shared copy-on-write. The properties the
tentpole rests on, checked here:

* allocator soundness — a page is never handed out twice, refcounts
  never go negative, and after every slot releases (and the prefix
  cache is cleared) all pages are free again: no leaks;
* COW prefix reuse is *bit-exact* — a paged session with shared (and
  exactly-identical) prompts emits the same tokens as the private-cache
  session, while skipping prefill for the shared pages;
* exhaustion is a typed, recoverable condition — `PoolExhausted` sheds
  to the queue (scripted via the `page_alloc_fail` fault or genuinely
  via a tiny pool) and only fails terminally when the request can never
  fit, with reason "pool_exhausted";
* the fault-recovery contract survives the layout swap — NaN corruption
  and wedge recovery still reproduce the fault-free tokens bit for bit;
* equal memory buys strictly more concurrency — a pool with half the
  private layout's KV capacity still serves the full slot complement.
"""

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # pragma: no cover
    from hypothesis_fallback import given, settings, strategies as st

from repro.runtime.kvpool import (PagePool, PagedKV, PoolExhausted,
                                  PrefixCache, TRASH_PAGE)

ARCH = "qwen3-14b-smoke"


# ----------------------------------------------------------------------------
# PagePool allocator invariants
# ----------------------------------------------------------------------------


def test_pool_basics():
    pool = PagePool(8, 4)
    assert pool.free_pages == 7                 # page 0 reserved
    pages = pool.alloc(3)
    assert len(set(pages)) == 3 and TRASH_PAGE not in pages
    assert pool.used_pages == 3
    freed = pool.release(pages)
    assert sorted(freed) == sorted(pages)
    assert pool.free_pages == 7


def test_pool_alloc_is_all_or_nothing():
    pool = PagePool(4, 4)
    pool.alloc(2)
    with pytest.raises(PoolExhausted) as ei:
        pool.alloc(2)
    assert ei.value.needed == 2 and ei.value.free == 1
    assert pool.free_pages == 1                 # nothing was taken
    assert pool.alloc_failures == 1


def test_shared_page_survives_first_release():
    pool = PagePool(4, 4)
    (p,) = pool.alloc(1)
    pool.ref([p])
    assert pool.release([p]) == []              # still referenced
    assert pool.release([p]) == [p]             # now free
    assert pool.refcount[p] == 0


@settings(deadline=None, max_examples=40)
@given(seed=st.integers(min_value=0, max_value=10_000),
       n_pages=st.integers(min_value=2, max_value=24),
       n_ops=st.integers(min_value=1, max_value=120))
def test_pool_never_double_allocates_or_leaks(seed, n_pages, n_ops):
    """Random alloc/ref/release interleavings: every live allocation set
    is disjoint, refcounts stay >= 0, and draining everything frees
    every page."""
    rng = np.random.default_rng(seed)
    pool = PagePool(n_pages, 4)
    live: list[list[int]] = []          # allocation units (owned refs)
    for _ in range(n_ops):
        op = rng.integers(0, 3)
        if op == 0:
            n = int(rng.integers(0, max(pool.free_pages, 1) + 1))
            try:
                pages = pool.alloc(n)
            except PoolExhausted:
                continue
            held = {p for unit in live for p in unit}
            assert not (set(pages) & held), "page double-allocated"
            live.append(pages)
        elif op == 1 and live:
            unit = live[int(rng.integers(0, len(live)))]
            if unit:
                pool.ref(unit)
                live.append(list(unit))
        elif op == 2 and live:
            unit = live.pop(int(rng.integers(0, len(live))))
            pool.release(unit)
        assert (pool.refcount >= 0).all()
        assert pool.refcount[TRASH_PAGE] == 1
        assert pool.free_pages + pool.used_pages == n_pages - 1
    for unit in live:
        pool.release(unit)
    assert pool.free_pages == n_pages - 1, "pages leaked"
    assert (pool.refcount[1:] == 0).all()


def test_dirty_tracking_scrubs_only_free_pages():
    pool = PagePool(8, 4)
    a = pool.alloc(2)
    b = pool.alloc(1)
    pool.mark_dirty(a + b)
    pool.release(a)
    assert sorted(pool.take_dirty_free()) == sorted(a)   # b still live
    assert pool.take_dirty_free() == []                  # marks cleared
    pool.release(b)
    assert pool.take_dirty_free() == b


# ----------------------------------------------------------------------------
# PrefixCache
# ----------------------------------------------------------------------------


def test_prefix_match_is_bit_exact_not_just_hash():
    pool = PagePool(8, 4)
    cache = PrefixCache(pool)
    toks = np.arange(8, dtype=np.int32)
    pages = pool.alloc(2)
    assert cache.insert(toks, pages) == 2
    assert cache.match(toks) == pages
    other = toks.copy()
    other[5] ^= 1
    assert cache.match(other) == pages[:1]      # second page differs
    assert cache.match(other[:3]) == []         # below one full page


def test_prefix_eviction_frees_pages():
    pool = PagePool(6, 4)
    cache = PrefixCache(pool)
    toks = np.arange(8, dtype=np.int32)
    pages = pool.alloc(2)
    cache.insert(toks, pages)
    pool.release(pages)                         # owner gone, cache holds
    assert pool.free_pages == 3
    freed = cache.evict(2)
    assert sorted(freed) == sorted(pages)
    assert pool.free_pages == 5
    assert cache.match(toks) == []


# ----------------------------------------------------------------------------
# PagedKV admission
# ----------------------------------------------------------------------------


@settings(deadline=None, max_examples=25)
@given(seed=st.integers(min_value=0, max_value=10_000),
       n_reqs=st.integers(min_value=1, max_value=12))
def test_paged_kv_admit_release_never_leaks(seed, n_reqs):
    rng = np.random.default_rng(seed)
    kv = PagedKV(n_pages=33, page_size=4, n_slots=4, pages_per_slot=8)
    live: list[int] = []
    for _ in range(n_reqs):
        if live and (len(live) == 4 or rng.integers(0, 2)):
            slot = live.pop(int(rng.integers(0, len(live))))
            if rng.integers(0, 2):
                kv.publish(slot)
            kv.release(slot)
            continue
        slot = next(s for s in range(4) if s not in live)
        prompt = rng.integers(1, 40, size=int(rng.integers(1, 16)))
        try:
            alloc = kv.admit(slot, prompt.astype(np.int32),
                             int(rng.integers(1, 8)))
        except PoolExhausted:
            continue
        table = alloc.table
        assert table.shape == (8,)
        n_live = len(kv.slot_pages(slot))
        assert (table[n_live:] == TRASH_PAGE).all()
        assert (table[:n_live] != TRASH_PAGE).all()
        live.append(slot)
    for slot in live:
        kv.release(slot)
    if kv.prefix is not None:
        kv.prefix.clear()
    assert kv.pool.free_pages == 32, "pages leaked"
    assert (kv.pool.refcount[1:] == 0).all()


def test_admit_shares_published_prefix_and_skips_prefill():
    kv = PagedKV(n_pages=33, page_size=4, n_slots=4, pages_per_slot=8)
    prompt = np.arange(1, 12, dtype=np.int32)       # 11 toks = 2 full pages
    a0 = kv.admit(0, prompt, 4)
    assert a0.shared_pages == 0 and a0.prefill_skip == 0
    kv.publish(0)
    kv.release(0)
    a1 = kv.admit(1, prompt, 4)
    assert a1.shared_pages == 2
    assert a1.prefill_skip == 8                     # 2 pages * 4 tokens
    assert a1.cow_copies == []                      # skip < prompt size
    assert kv.slot_pages(1)[:2] == kv.slot_pages(1)[:2]
    kv.release(1)


def test_exact_full_coverage_prompt_cow_forks_last_page():
    kv = PagedKV(n_pages=33, page_size=4, n_slots=4, pages_per_slot=8)
    prompt = np.arange(1, 9, dtype=np.int32)        # exactly 2 pages
    kv.admit(0, prompt, 4)
    kv.publish(0)
    first_pages = kv.slot_pages(0)
    kv.release(0)
    a1 = kv.admit(1, prompt, 4)
    assert a1.shared_pages == 2
    assert a1.prefill_skip == 7                     # last token re-fed
    assert len(a1.cow_copies) == 1
    src, dst = a1.cow_copies[0]
    assert src == first_pages[1]                    # forked shared page
    assert kv.slot_pages(1)[1] == dst != src
    assert kv.pool.refcount[src] > 0                # src alive until copy
    kv.release(1)


def test_admit_allocates_nothing_on_failure():
    kv = PagedKV(n_pages=5, page_size=4, n_slots=2, pages_per_slot=8,
                 prefix_cache=False)
    kv.admit(0, np.arange(8, dtype=np.int32), 4)    # 3 pages of 4
    free_before = kv.pool.free_pages
    with pytest.raises(PoolExhausted):
        kv.admit(1, np.arange(8, dtype=np.int32), 4)
    assert kv.pool.free_pages == free_before
    assert kv.slot_pages(1) == []


def test_admit_evicts_prefix_cache_under_pressure():
    kv = PagedKV(n_pages=7, page_size=4, n_slots=2, pages_per_slot=8)
    kv.admit(0, np.arange(8, dtype=np.int32), 4)    # 3 pages
    kv.publish(0)
    kv.release(0)                                   # 2 pages cached
    # a disjoint prompt needs more than the raw free pages — eviction of
    # the cached prefix must make room
    alloc = kv.admit(1, 50 + np.arange(12, dtype=np.int32), 8)   # 5 pages
    assert alloc.shared_pages == 0
    assert len(kv.slot_pages(1)) == 5
    kv.release(1)


def test_reset_forgets_everything():
    kv = PagedKV(n_pages=33, page_size=4, n_slots=4, pages_per_slot=8)
    kv.admit(0, np.arange(1, 12, dtype=np.int32), 4)
    kv.publish(0)
    kv.reset()
    assert kv.pool.free_pages == 32
    assert kv.slot_pages(0) == []
    assert kv.match_len(np.arange(1, 12, dtype=np.int32)) == 0


# ----------------------------------------------------------------------------
# End-to-end serving: paged vs private, faults, capacity
# ----------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cluster():
    from repro.cluster.session import Cluster
    return Cluster(ARCH)


@pytest.fixture(scope="module")
def programs(cluster):
    from repro.cluster.session import ServeSessionProgram
    common = dict(slots=4, max_seq=48, max_prompt=16, max_new=6, chunk=4)
    private = cluster.compile(ServeSessionProgram(preempt=False, **common))
    paged = cluster.compile(ServeSessionProgram(paged=True, page_size=4,
                                                **common))
    return private, paged, private.init_params()


def _run(prog, params, prompts, faults=None, max_new=6):
    sess = prog.open(params=params, faults=faults)
    handles = [sess.submit(p, max_new) for p in prompts]
    sess.drain()
    return [h.result() for h in handles], sess.stats()


def test_paged_bit_identical_on_prefix_free_workload(programs):
    private, paged, params = programs
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 50, size=int(rng.integers(2, 16)))
               .astype(np.int32) for _ in range(6)]
    toks_p, _ = _run(private, params, prompts)
    toks_g, st = _run(paged, params, prompts)
    for a, b in zip(toks_p, toks_g):
        np.testing.assert_array_equal(a, b)
    assert st["kv"]["alloc_failures"] == 0


def _float32_logits(cfg, params, tokens):
    """Float32 logits for the token after `tokens`, teacher-forced through
    the private-cache decode step with every Pallas kernel off."""
    import jax.numpy as jnp

    from repro.models import steps
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32)
                                 if a.dtype == jnp.bfloat16 else a, t)
    step = jax.jit(steps.make_decode_step(cfg, max_seq=48, logits=True,
                                          policy="reference"))
    cache, params = f32(steps.init_cache(cfg, 1, 48)), f32(params)
    for t, tok in enumerate(tokens):
        cache, logits = step(params, cache,
                             {"tokens": jnp.full((1, 1), tok, jnp.int32),
                              "pos": jnp.full((1,), t, jnp.int32)})
    return np.asarray(logits[0])


def test_paged_kernel_and_gather_route_serve_the_same_tokens(cluster,
                                                             programs):
    """The default policy serves paged attention through the paged decode
    kernel (interpreted off a TPU); a "reference" override for it, through
    the gather-then-mask composition. Requests of mixed lengths, most
    spanning several 4-row pages, get the same greedy tokens from both,
    and each policy's counter shows the route it traced.

    The two routes round the softmax weights to bf16 at different points,
    so where the model's two best tokens lie within bf16 noise of each
    other (random smoke weights make such near-ties common: a logit gap of
    0.008 on this mix) either route may take either token. Where the
    routes part, the float32 model must hold the two tokens within TIE of
    each other and of its best; that request is compared no further."""
    from repro.cluster.policy import KernelPolicy
    from repro.cluster.session import Cluster, ServeSessionProgram
    from repro.configs import get
    tie = 0.02
    _, paged, params = programs
    gather = Cluster(ARCH, policy=KernelPolicy(
        overrides={"paged_decode_attention": "reference"}))
    gather_prog = gather.compile(ServeSessionProgram(
        paged=True, page_size=4, slots=4, max_seq=48, max_prompt=16,
        max_new=6, chunk=4))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 50, size=n).astype(np.int32)
               for n in (1, 3, 4, 5, 9, 16, 12, 7)]
    toks_k, _ = _run(paged, params, prompts)
    toks_g, _ = _run(gather_prog, params, prompts)
    agreed = 0
    for prompt, a, b in zip(prompts, toks_k, toks_g):
        split = next((j for j in range(len(a)) if a[j] != b[j]), None)
        if split is None:
            agreed += len(a)
            continue
        agreed += split
        logits = _float32_logits(get(ARCH), params,
                                 [*prompt, *a[:split]])
        pair = logits[[a[split], b[split]]]
        assert logits.max() - pair.min() < tie, (prompt, split, pair)
    assert agreed >= 0.8 * sum(len(a) for a in toks_k)
    assert cluster.kernel_policy.stats.get("pallas_calls", 0) > 0
    assert gather.kernel_policy.stats.get("ref_calls", 0) > 0


@pytest.mark.parametrize("arch,n_layers", [
    (ARCH, None),
    # 2 scanned periods of 4 pooled attn layers + 1 cross layer whose
    # K/V stay private, then 2 unscanned remainder attn layers
    ("llama-3.2-vision-90b-smoke", 12),
    # pooled K/V and private recurrent state (SSM state, conv tail) in
    # every one of 4 layers
    ("falcon-h1-34b-smoke", 4),
])
def test_every_layer_writes_its_own_pool_region(arch, n_layers):
    """Mid-generation, every pool leaf's layer l holds, at each live
    slot's pages, exactly the K/V rows the private-cache session holds in
    its layer l — a write into another layer's region fails here even
    where the tokens happen to agree; private leaves next to the pool
    leaves match the private run as they are."""
    import dataclasses

    from repro.cluster.policy import KernelPolicy
    from repro.cluster.session import Cluster, ServeSessionProgram
    from repro.configs import get
    from repro.models import steps

    cfg = get(arch)
    if n_layers:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    _, n_super, remainder = steps.block_plan(cfg)
    assert n_super >= 2
    # the write path is the same under either attention route; with the
    # gather-then-mask route the paged session's arithmetic is the private
    # one's, so each later layer's rows can be compared bit for bit
    cluster = Cluster(cfg, policy=KernelPolicy(
        overrides={"paged_decode_attention": "reference"}))
    common = dict(slots=4, max_seq=48, max_prompt=16, chunk=4)
    private = cluster.compile(ServeSessionProgram(preempt=False, **common))
    paged = cluster.compile(ServeSessionProgram(paged=True, page_size=4,
                                                **common))
    params = private.init_params()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 50, size=int(rng.integers(2, 10)))
               .astype(np.int32) for _ in range(4)]
    sessions = [prog.open(params=params) for prog in (private, paged)]
    for sess in sessions:
        for p in prompts:
            sess.submit(p, 20)
        for _ in range(3):                       # 12 steps: all still live
            sess.poll()
    sp, sg = (s.state for s in sessions)
    pos = np.asarray(sg["pos"])
    np.testing.assert_array_equal(pos, np.asarray(sp["pos"]))
    assert pos.min() > 4                          # rows on several pages
    rows = np.arange(pos.max())
    tables = np.asarray(sg["pages"])
    mask = steps.paged_cache_mask(cfg, 1, 1)
    groups = [("blocks", True)] + ([("rem", False)] if remainder else [])
    n_pool = 0
    for group, stacked in groups:
        for (path, m), want, got in zip(
                jax.tree.leaves_with_path(mask[group]),
                jax.tree.leaves(sp["cache"][group]),
                jax.tree.leaves(sg["cache"][group])):
            want, got = np.asarray(want), np.asarray(got)
            if not m:
                np.testing.assert_array_equal(got, want, err_msg=str(path))
                continue
            n_pool += 1
            if not stacked:
                want, got = want[None], got[None]
            for l in range(want.shape[0]):
                for b in range(len(pos)):
                    r = rows[:pos[b]]
                    np.testing.assert_array_equal(
                        got[l][tables[b, r // 4], r % 4], want[l][b, r],
                        err_msg=f"{group}{path} layer {l} slot {b}")
    assert n_pool >= 2


def test_shared_prefix_skips_prefill_bit_identically(programs):
    private, paged, params = programs
    rng = np.random.default_rng(1)
    pre = rng.integers(1, 50, size=12).astype(np.int32)   # 3 full pages
    prompts = [np.concatenate([pre,
                               rng.integers(1, 50, size=3).astype(np.int32)])
               for _ in range(8)]
    toks_p, _ = _run(private, params, prompts)
    toks_g, st = _run(paged, params, prompts)
    for a, b in zip(toks_p, toks_g):
        np.testing.assert_array_equal(a, b)
    kv = st["kv"]
    assert kv["prefix_hits"] > 0
    assert kv["pages_shared"] > 0
    assert kv["prefill_skipped_tokens"] >= kv["prefix_hits"] * 12


def test_identical_prompts_cow_fork_bit_identically(programs):
    private, paged, params = programs
    rng = np.random.default_rng(2)
    pre = rng.integers(1, 50, size=12).astype(np.int32)   # exact page cover
    prompts = [pre.copy() for _ in range(6)]
    toks_p, _ = _run(private, params, prompts)
    toks_g, st = _run(paged, params, prompts)
    for a, b in zip(toks_p, toks_g):
        np.testing.assert_array_equal(a, b)
    assert st["kv"]["cow_forks"] > 0


def test_page_alloc_fault_sheds_and_requeues(programs):
    from repro.runtime.faults import FaultPlan
    _, paged, params = programs
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 50, size=12).astype(np.int32)
               for _ in range(6)]
    toks_ref, _ = _run(paged, params, prompts)
    plan = FaultPlan().page_alloc_fail(at_chunk=0)
    toks_f, st = _run(paged, params, prompts, faults=plan)
    for a, b in zip(toks_ref, toks_f):
        np.testing.assert_array_equal(a, b)
    assert st["kv"]["pool_exhausted"] == 4      # the whole first wave shed
    assert plan.summary()["by_kind"]["page_alloc_fail"] == 1


def test_genuine_exhaustion_backs_off_and_completes(cluster, programs):
    from repro.cluster.session import ServeSessionProgram
    _, _, params = programs
    # 10 usable pages, 5 per request: two slots' worth — the other two
    # admissions must shed, requeue, and run as pages free up
    prog = cluster.compile(ServeSessionProgram(
        slots=4, max_seq=48, max_prompt=16, max_new=6, chunk=4,
        paged=True, page_size=4, n_pages=11, prefix_cache=False))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 50, size=12).astype(np.int32)
               for _ in range(6)]
    sess = prog.open(params=params)
    handles = [sess.submit(p, 6) for p in prompts]
    sess.drain()
    assert all(h.ok for h in handles)
    assert sess.stats()["kv"]["pool_exhausted"] > 0


def test_never_fitting_request_fails_typed(cluster, programs):
    from repro.cluster.session import ServeSessionProgram
    _, _, params = programs
    prog = cluster.compile(ServeSessionProgram(
        slots=2, max_seq=48, max_prompt=16, max_new=20, chunk=4,
        paged=True, page_size=4, n_pages=3, prefix_cache=False))
    sess = prog.open(params=params)
    h = sess.submit(np.arange(1, 13, dtype=np.int32), 20)
    sess.drain()
    assert h.failed
    assert h.fail_reason == "pool_exhausted"


def test_nan_corruption_recovers_bit_identically_under_paged(programs):
    from repro.runtime.faults import FaultPlan
    _, paged, params = programs
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, 50, size=12).astype(np.int32)
               for _ in range(6)]
    toks_ref, _ = _run(paged, params, prompts)
    plan = FaultPlan().corrupt_nan(at_chunk=1, slot=0)
    toks_f, _ = _run(paged, params, prompts, faults=plan)
    for a, b in zip(toks_ref, toks_f):
        np.testing.assert_array_equal(a, b)
    assert plan.summary()["by_kind"]["corrupt_nan"] == 1


def test_wedge_recovery_resets_pool_under_paged(programs):
    from repro.runtime.faults import FaultPlan, SessionWedged
    _, paged, params = programs
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 50, size=12).astype(np.int32)
               for _ in range(6)]
    toks_ref, _ = _run(paged, params, prompts)
    plan = FaultPlan().wedge(at_chunk=1)
    sess = paged.open(params=params, faults=plan)
    handles = [sess.submit(p, 6) for p in prompts]
    with pytest.raises(SessionWedged):
        sess.drain(timeout_s=0.5)
    sess.recover_wedged()
    # recovery rebuilt the device pool: the kv book must match (empty)
    assert sess.stats()["kv"]["used_pages"] == 0
    sess.drain()
    for a, h in zip(toks_ref, handles):
        np.testing.assert_array_equal(a, h.result())


def test_half_memory_pool_serves_full_slot_complement(cluster, programs):
    """Equal memory buys strictly more concurrency: a pool with HALF the
    private layout's page capacity still runs all 4 slots at once when
    requests are shorter than max_seq (the private layout reserves
    max_seq rows per slot no matter what)."""
    from repro.cluster.session import ServeSessionProgram
    _, _, params = programs
    pps = -((48 + 1) // -4)                      # private capacity/slot
    half = 4 * pps // 2 + 1
    prog = cluster.compile(ServeSessionProgram(
        slots=4, max_seq=48, max_prompt=16, max_new=6, chunk=4,
        paged=True, page_size=4, n_pages=half, prefix_cache=False))
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 50, size=10).astype(np.int32)
               for _ in range(4)]
    sess = prog.open(params=params)
    handles = [sess.submit(p, 6) for p in prompts]
    sess.drain()
    assert all(h.ok for h in handles)
    # all four ran concurrently: nothing was shed back to the queue
    assert sess.stats()["kv"]["pool_exhausted"] == 0


def test_paged_rejects_preempt_and_recurrent_archs(cluster):
    from repro.cluster.session import Cluster, ServeSessionProgram
    from repro.models import steps
    from repro.configs import get as get_arch
    # recurrent-only arch has no pageable leaves
    cfg = get_arch("xlstm-125m-smoke")
    with pytest.raises(ValueError):
        steps.paged_cache_specs(cfg, 2, 16, n_pages=9, page_size=4)
    # preempt + kv is contradictory at the session layer
    from repro.runtime.serve_loop import ServeSession
    prog = cluster.compile(ServeSessionProgram(
        slots=2, max_seq=32, max_prompt=8, chunk=4, paged=True,
        page_size=4, preempt=True))
    sess = prog.open()          # program forces preempt off: must not raise
    assert sess.stats()["kv"]["used_pages"] == 0


# ----------------------------------------------------------------------------
# Recurrent state beside the pool (Falcon-H1: attention and Mamba-2 per layer)
# ----------------------------------------------------------------------------

HYBRID = "falcon-h1-34b-smoke"


@pytest.fixture(scope="module")
def hybrid_programs():
    """Smoke widths with a mixer whose state matters: no multiplier
    scales its inputs or output down, its output is the state's alone
    (D = 0) and decays slowly (A = -exp(-4)), so a state left over from an
    earlier request, or missing tokens, changes the served tokens."""
    import dataclasses

    from repro.cluster.session import Cluster, ServeSessionProgram
    from repro.configs import get
    cluster = Cluster(dataclasses.replace(
        get(HYBRID), ssm_in_mult=1.0, ssm_out_mult=1.0, ssm_mults=(1.0,) * 5))
    common = dict(slots=2, max_seq=64, max_prompt=48, chunk=4)
    private = cluster.compile(ServeSessionProgram(preempt=False, **common))
    paged = cluster.compile(ServeSessionProgram(paged=True, page_size=8,
                                                **common))
    params = private.init_params()
    ssm = params["blocks"]["sub0"]["ssm"]
    ssm["A_log"] = jax.numpy.full_like(ssm["A_log"], -4.0)
    ssm["D"] = jax.numpy.zeros_like(ssm["D"])
    return private, paged, params


def test_recurrent_arch_pages_kv_but_skips_no_prefix(hybrid_programs):
    """Shared pages cannot restore the recurrent state of the tokens they
    hold: with recurrent state in the cache, admission matches and
    publishes no prefix, every prompt token is prefilled, and the tokens
    are the private-cache session's."""
    private, paged, params = hybrid_programs
    rng = np.random.default_rng(5)
    system = rng.integers(0, 256, 16, dtype=np.int32)       # two pages
    prompts = [system,
               np.concatenate([system, rng.integers(0, 256, 7,
                                                    dtype=np.int32)]),
               system]
    served = []
    for prog in (private, paged):
        sess = prog.open(params=params)
        toks = []
        for p in prompts:                   # one at a time: the first
            h = sess.submit(p, 6)           # finishes (and would publish)
            while not h.done:               # before the next is admitted
                sess.poll()
            toks.append(h.result())
        served.append(toks)
        kv = sess.stats().get("kv")
    assert kv["prefix_reuse"] == "off: recurrent state"
    assert kv["prefill_skipped_tokens"] == 0 and kv["pages_shared"] == 0
    assert "prefix_hits" not in kv
    for a, b in zip(*served):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(served[1][0], served[1][2])


def test_reused_slot_serves_as_a_fresh_session(hybrid_programs):
    """A slot that served a long request is zeroed on refill: the next
    request in it gets the tokens a fresh session gives it."""
    _, paged, params = hybrid_programs
    rng = np.random.default_rng(6)
    long_p = rng.integers(0, 256, 40, dtype=np.int32)
    short = rng.integers(0, 256, 5, dtype=np.int32)
    sess = paged.open(params=params)
    first = [sess.submit(long_p, 20), sess.submit(long_p[::-1].copy(), 20)]
    sess.drain()
    assert all(h.done for h in first)
    reused = sess.submit(short, 8)
    sess.drain()
    fresh = paged.open(params=params)
    h = fresh.submit(short, 8)
    fresh.drain()
    np.testing.assert_array_equal(reused.result(), h.result())
    assert paged.open(params=params).stats()["kv"]["prefix_reuse"] == \
        "off: recurrent state"
