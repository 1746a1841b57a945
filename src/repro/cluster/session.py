"""Cluster/Session façade — one object owning mesh, addressing, kernel
policy, and compiled programs.

MemPool's programmability claim is that 256 cores with one shared L1 view
are driven through multiple runtimes over a *single* substrate; the
follow-up "Flavors" work configures that one substrate per workload. This
module is the substrate object for the TPU translation:

    cluster = Cluster("qwen3-14b-smoke")            # arch + mesh + rules
    with cluster.policy("fused"):                   # kernel policy scope
        train = cluster.compile(TrainProgram(num_steps=100))
    report = train.run()                            # .plan() / .report() too

`Cluster` owns the ArchConfig, the mesh, the hybrid-addressing rules, the
KERNEL_TUNES view, and a CompileCache; `cluster.compile(spec)` turns a
program spec (TrainProgram / ServeSessionProgram / DryRunProgram /
BenchProgram) into a Program with `.run()`, `.plan()`, and `.report()`.
Every entrypoint (`repro.api`, `launch/train.py`, `launch/dryrun.py`,
`benchmarks/run.py`, the examples) is a thin wrapper over these objects,
so later subsystems (continuous batching, multi-cluster, backend
selection) plug into one place instead of five.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterator

import jax
import numpy as np

from repro.cluster import cells
from repro.cluster.policy import KernelPolicy, as_policy, use_policy
from repro.configs import get as get_arch
from repro.configs.registry import (ArchConfig, SHAPES, cell_supported,
                                    kernel_tunes)
from repro.core import addressing, compat
from repro.kernels import tunedb
from repro.models import steps
from repro.runtime import CompileCache, TrainLoop, TrainLoopConfig, engine


# ----------------------------------------------------------------------------
# Program specs — frozen descriptions, compiled by Cluster.compile
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TrainProgram:
    """A training run on the synthetic stream, region-planned on the mesh."""

    num_steps: int = 100
    batch: int = 4
    seq: int = 128
    seed: int = 0
    checkpoint_dir: str = "/tmp/repro-train"
    checkpoint_every: int | None = None    # None -> max(num_steps // 2, 1)
    log_every: int | None = None           # None -> max(num_steps // 10, 1)
    warmup: int | None = None              # None -> max(num_steps // 10, 1)
    resume: bool = False                   # restore latest checkpoint first
    double_buffer: bool = False            # prefetch feed (DMA analogue)
    steps_per_sync: int = 1                # steps per scan-compiled chunk
    #   (> 1: host syncs once per chunk; straggler/logging sample at chunk
    #   granularity; state donated through the chunk — engine.py)


@dataclasses.dataclass(frozen=True)
class ServeSessionProgram:
    """Request-level serving: a slot pool with continuous batching.

    Compiles to a `CompiledServeSession`; `open()` returns a live
    `ServeSession` with `submit(prompt, max_new, klass=..., deadline_s=...)
    -> RequestHandle`, `poll()`/`stream()` for incremental tokens,
    `cancel(handle)`, and `drain()`. `run()` is the one-shot path (fill
    the pool with one batch, drain, return the tokens and the stats).

    The SLO/robustness knobs configure the session's priority admission
    (`shed_watermark`, `aging_rounds`), slot preemption (`preempt`), the
    per-chunk device-wait watchdog (`watchdog_s` -> `SessionWedged`),
    fault recovery (`max_retries`, `retry_backoff_s`), and the NaN
    corruption sentinel (`nan_check`); `open(faults=FaultPlan(...))` arms
    scripted fault injection for chaos runs.

    `paged=True` swaps the per-slot private KV layout for the shared
    paged pool (runtime/kvpool.py): attention K/V lives in one global
    page array, slots hold page tables, refill installs tables instead
    of zeroing cache rows, and shared prompt prefixes are reused
    copy-on-write so repeated preambles skip prefill entirely. Paged
    sessions run with preemption off (slot snapshots do not carry page
    tables) and require an arch with positional attention (windowed /
    recurrent-only archs keep their private layout and reject `paged`).
    An arch whose layers also keep recurrent state (Falcon-H1's Mamba-2
    mixer) pages its K/V but reuses no prefix: shared pages cannot restore
    the state of the tokens they hold.
    """

    slots: int = 4                         # slot-pool size (batch rows)
    max_seq: int = 64
    max_prompt: int = 8                    # per-slot prompt buffer length
    max_new: int = 16                      # one-shot run() / submit default
    seed: int = 0
    eos_id: int | None = None
    chunk: int = 16                        # decode steps per host sync
    max_queue: int | None = None           # bounded-queue backpressure
    admission: str = "fifo"                # or "longest_prefix"
    shed_watermark: int | None = None      # total queue depth that sheds
    #   best-effort work (latency/throughput get QueueFull instead)
    aging_rounds: int = 8                  # anti-starvation: +1 effective
    #   class rank per this many admission rounds waited
    preempt: bool = True                   # latency may checkpoint + evict
    #   a lower-class running slot (bit-identical resume)
    watchdog_s: float | None = None        # per-chunk device-wait bound;
    #   None = wait forever (poll(timeout_s=...) still overrides)
    max_retries: int = 2                   # fault-recovery restarts per
    #   request before it fails with "retries_exhausted"
    retry_backoff_s: float = 0.05          # base of the exponential
    #   re-admission backoff after a fault restart
    nan_check: bool = False                # scan cache rows for NaN every
    #   chunk (auto-on when a FaultPlan scripts corruption)
    paged: bool = False                    # shared paged KV pool with COW
    #   prefix reuse (forces preempt off; see class docstring)
    page_size: int = 16                    # tokens per KV page
    n_pages: int | None = None             # pool size; None -> slots *
    #   pages_per_slot + 1 (trash page), i.e. private-layout capacity
    prefix_cache: bool = True              # publish finished prompts for
    #   COW prefix reuse across requests
    snapshot_every: int | None = None      # chunks between bit-exact
    #   session snapshots (needs open(durable_dir=...)); None = journal-only
    journal_fsync: bool | int = True       # True/False/every-K (see Journal)
    scrub_pages: int = 2                   # stamped pages re-verified per
    #   boundary by the background integrity scrub (paged; 0 disables)


@dataclasses.dataclass(frozen=True)
class ShardedServeSessionProgram(ServeSessionProgram):
    """Cluster-of-clusters serving: `groups` full session cells behind
    one `submit/poll/stream/cancel/drain` surface.

    Mirrors MemPool's tiles -> groups -> cluster hierarchy on the device
    mesh: each serving group owns a complete session cell (slot pool,
    paged KV pool + prefix cache, stall ledger, journal) pinned to its
    own device, and a two-level scheduler places each request in a group
    (locality-aware: warm prefix-cache overlap + load, scored with the
    paper's topology model) before the group's own slot scheduler takes
    over. All `ServeSessionProgram` knobs apply *per group* — e.g.
    `slots=4, groups=2` is 8 slots total, two pools of 4.

    `open()` returns a `runtime.ShardedServeSession`; with `groups=1` it
    is token-for-token identical to `ServeSessionProgram.open()` (same
    cell, same scheduler, a trivial placement layer) and its durable
    directory stays restorable by either program. `run()` (the one-shot
    path) is not defined for sharded sessions.
    """

    groups: int = 2                        # serving groups (session cells)


@dataclasses.dataclass(frozen=True)
class DryRunProgram:
    """Lower + compile one (arch x shape) cell on this cluster's mesh and
    extract memory/cost/collective analysis — no allocation."""

    shape: str = "train_4k"
    fsdp_gather: bool = False
    decode_chunk: int = 1                  # session cells: decode steps per
    #   chunk (> 1 without `session` is refused: no program runs that cell)
    session: bool = False                  # decode shapes: lower the slot-
    #   scheduled session cell (donated pool state) instead
    paged: bool = False                    # session shapes: lower the
    #   shared-paged-KV session cell (page tables in state)
    page_size: int = 16                    # tokens per KV page (paged)


@dataclasses.dataclass(frozen=True)
class BenchProgram:
    """The paper-figure benchmark sweep, run under this cluster's policy."""

    sections: tuple[str, ...] = ()         # () -> every module offered
    smoke: bool = False
    repeat: int = 1


# ----------------------------------------------------------------------------
# Cluster
# ----------------------------------------------------------------------------


class Cluster:
    """The substrate: arch + mesh + addressing + kernel policy + programs.

    `arch` may be an arch name (``"qwen3-14b-smoke"``), an ArchConfig, or
    None for a kernel-only cluster (policy + tunes + bench programs, no
    model). `mesh` defaults to all local devices on a (data, model) mesh.

    `tune_db` is the persistent timed-tune database: a
    `kernels.tunedb.TuneDB`, a path to open one, or None to fall back to
    the ``REPRO_TUNE_DB`` env default (which may itself be unset — no
    persistence). When a DB resolves, the cluster warm-starts
    KERNEL_TUNES from it (so `tuned_call` hits instead of racing) and
    installs it as the active write-through target for new races; the
    warm-start count is kept in ``tune_db_warm`` and surfaced by
    `Program.report()` alongside the policy's tune_hits/misses/races.
    """

    def __init__(self, arch: "str | ArchConfig | None" = None, mesh=None, *,
                 policy: "KernelPolicy | str | None" = None,
                 rules_overrides=None,
                 tune_db: "tunedb.TuneDB | str | None" = None):
        self.arch: ArchConfig | None = (
            get_arch(arch) if isinstance(arch, str) else arch)
        self.mesh = mesh if mesh is not None else compat.make_mesh(
            (jax.device_count(), 1), ("data", "model"))
        if rules_overrides is None:
            rules_overrides = (self.arch.rules_overrides if self.arch
                               else ())
        self.rules = addressing.default_rules(self.mesh,
                                              overrides=rules_overrides)
        self._policy = as_policy(policy)
        self.compile_cache = CompileCache()
        self.tune_db = tunedb.resolve_db(tune_db)
        self.tune_db_warm = 0
        if self.tune_db is not None:
            self.tune_db_warm = self.tune_db.warm_start(
                backend=jax.default_backend(), mode=self._policy.mode)
            tunedb.set_active_db(self.tune_db)

    # -- kernel policy --------------------------------------------------------
    @property
    def kernel_policy(self) -> KernelPolicy:
        return self._policy

    def policy(self, policy: "KernelPolicy | str | None" = None, **kwargs):
        """Scope a kernel policy on this cluster::

            with cluster.policy("fused"):              # a mode string
            with cluster.policy(mode="tuned", overrides={"matmul": "reference"}):

        Inside the block the policy is both the ambient one (kernel dispatch
        reads it) and the cluster default captured by `compile`.
        """
        if policy is None:
            pol = KernelPolicy(**kwargs) if kwargs else self._policy
        else:
            pol = as_policy(policy)
            if kwargs:
                pol = dataclasses.replace(pol, **kwargs)
        return _PolicyScope(self, pol)

    def tunes(self, kernel: str | None = None) -> list:
        """This cluster's view of the autotune records (KERNEL_TUNES)."""
        recs = kernel_tunes()
        if kernel is not None:
            recs = [r for r in recs if r.kernel == kernel]
        return recs

    # -- addressing plan ------------------------------------------------------
    def plan(self) -> dict[str, Any]:
        """The hybrid addressing plan for this cluster's arch on its mesh:
        {tree path: {logical, spec, region, shape}} for every parameter."""
        cfg = self._require_arch("plan")
        p_sds, p_log = steps.abstract_params(cfg)
        out = {}
        for (path, sds), (_, logical) in zip(
                jax.tree_util.tree_flatten_with_path(p_sds)[0],
                jax.tree_util.tree_flatten_with_path(
                    p_log, is_leaf=lambda x: isinstance(x, tuple))[0]):
            key = "/".join(str(getattr(k, "key", k)) for k in path)
            spec = self.rules.spec_for(logical, sds.shape, self.mesh)
            region = ("REPLICATED" if not [s for s in spec if s] else
                      "INTERLEAVED" if any(n in ("embed", "ffn", "heads",
                                                 "kv_heads", "vocab",
                                                 "expert")
                                           for n in logical if n) else
                      "SEQUENTIAL")
            out[key] = {"logical": logical, "spec": spec, "region": region,
                        "shape": sds.shape}
        return out

    def state_shardings(self, tree_sds, tree_logical):
        return cells.shardings_for(tree_sds, tree_logical, self.mesh,
                                   self.rules)

    # -- compilation ----------------------------------------------------------
    def compile(self, spec) -> "Program":
        """Program spec -> compiled Program, memoized in the compile cache
        keyed on (spec, arch, mesh, policy knobs)."""
        builders = {TrainProgram: CompiledTrain,
                    ServeSessionProgram: CompiledServeSession,
                    ShardedServeSessionProgram: CompiledShardedServeSession,
                    DryRunProgram: CompiledDryRun, BenchProgram: CompiledBench}
        try:
            builder = builders[type(spec)]
        except KeyError:
            raise TypeError(f"Cluster.compile expects a program spec, got "
                            f"{type(spec).__name__}") from None
        key = (type(spec).__name__, spec,
               self.arch.name if self.arch else None,
               tuple(self.mesh.shape.items())
               if hasattr(self.mesh.shape, "items") else self.mesh.shape,
               self._policy.fingerprint())
        return self.compile_cache.get(key,
                                      lambda: builder(self, spec,
                                                      self._policy))

    def _require_arch(self, what: str) -> ArchConfig:
        if self.arch is None:
            raise ValueError(f"{what} needs an architecture; this is a "
                             f"kernel-only Cluster (arch=None)")
        return self.arch


class _PolicyScope:
    def __init__(self, cluster: Cluster, pol: KernelPolicy):
        self._cluster = cluster
        self._pol = pol
        self._prev: KernelPolicy | None = None
        self._cm = None

    def __enter__(self) -> KernelPolicy:
        self._prev = self._cluster._policy
        self._cluster._policy = self._pol
        self._cm = use_policy(self._pol)
        return self._cm.__enter__()

    def __exit__(self, *exc):
        try:
            return self._cm.__exit__(*exc)
        finally:
            self._cluster._policy = self._prev


# ----------------------------------------------------------------------------
# Compiled programs
# ----------------------------------------------------------------------------


class Program:
    """A compiled program bound to its cluster: `.run()`, `.plan()`,
    `.report()`. Subclasses hold the actual compiled step functions."""

    kind = "program"

    def __init__(self, cluster: Cluster, spec, policy: KernelPolicy):
        self.cluster = cluster
        self.spec = spec
        self.policy = policy
        self._last_run: dict | None = None

    def run(self, **kwargs) -> dict:
        raise NotImplementedError

    def plan(self) -> dict:
        return self.cluster.plan()

    def report(self) -> dict:
        """Program metadata + (when run) a result summary."""
        mesh = self.cluster.mesh
        out = {
            "kind": self.kind,
            "arch": self.cluster.arch.name if self.cluster.arch else None,
            "mesh": dict(mesh.shape.items())
            if hasattr(mesh.shape, "items") else mesh.shape,
            "spec": dataclasses.asdict(self.spec),
            "policy": self.policy.describe(),
            "compile_cache": {"hits": self.cluster.compile_cache.hits,
                              "misses": self.cluster.compile_cache.misses},
        }
        if self.cluster.tune_db is not None:
            out["tunedb"] = dict(self.cluster.tune_db.describe(),
                                 warm_started=self.cluster.tune_db_warm)
        if self._last_run is not None:
            out["result"] = {k: v for k, v in self._last_run.items()
                             if k != "params"}
        return out


class CompiledTrain(Program):
    kind = "train"

    def __init__(self, cluster, spec: TrainProgram, policy):
        super().__init__(cluster, spec, policy)
        cfg = cluster._require_arch("TrainProgram")
        n = spec.num_steps
        warmup = spec.warmup if spec.warmup is not None else max(n // 10, 1)
        raw_step = steps.make_train_step(cfg,
                                         schedule_kwargs={"warmup": warmup,
                                                          "total": n},
                                         policy=policy)
        self.step: Callable = jax.jit(raw_step, donate_argnums=0)
        # scan-of-steps engine program (state donated through the chunk)
        self.chunk: Callable | None = (
            engine.make_train_chunk(raw_step)
            if spec.steps_per_sync > 1 else None)

    def init_state(self, seed: int | None = None):
        cfg = self.cluster.arch
        seed = self.spec.seed if seed is None else seed
        state = steps.init_train_state(cfg, jax.random.PRNGKey(seed),
                                       max_seq=self.spec.seq)
        sh = self._state_shardings(state)
        return jax.tree.map(jax.device_put, state, sh), sh

    def _state_shardings(self, state):
        state_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        _, state_log = steps.abstract_train_state(self.cluster.arch,
                                                  self.spec.seq)
        return self.cluster.state_shardings(state_sds, state_log)

    def _feed(self, batch_sh):
        from repro.data import (Distributor, DoubleBufferedFeed, Splitter,
                                SyntheticLMStream)
        from repro.data.pipeline import BatchSpec

        cfg, spec = self.cluster.arch, self.spec
        stream = SyntheticLMStream(BatchSpec(spec.batch, spec.seq, cfg.vocab),
                                   seed=spec.seed)
        dist = Distributor(self.cluster.mesh,
                           Splitter(self.cluster.mesh, ("data",)))
        if spec.double_buffer:
            # chunked stepping drains steps_per_sync batches per dispatch;
            # the ring must hold a full chunk or the drain blocks on the
            # producer and un-hides the transfers it exists to hide
            return DoubleBufferedFeed(
                lambda s: dist.materialize(stream, s, batch_sh),
                depth=max(2, spec.steps_per_sync))

        def batches() -> Iterator[dict]:
            step = 0
            while True:
                yield dist.materialize(stream, step, batch_sh)
                step += 1

        return batches()

    def run(self) -> dict:
        spec = self.spec
        mesh, rules = self.cluster.mesh, self.cluster.rules
        n = spec.num_steps
        state, state_sh = self.init_state()
        batch_sh = jax.sharding.NamedSharding(
            mesh, rules.spec_for(("batch", "seq"), (spec.batch, spec.seq),
                                 mesh))
        feed = self._feed(batch_sh)
        loop = TrainLoop(
            TrainLoopConfig(
                total_steps=n,
                checkpoint_every=(spec.checkpoint_every
                                  if spec.checkpoint_every is not None
                                  else max(n // 2, 1)),
                log_every=(spec.log_every if spec.log_every is not None
                           else max(n // 10, 1)),
                checkpoint_dir=spec.checkpoint_dir,
                steps_per_sync=spec.steps_per_sync),
            self.step, state, feed, state_shardings=state_sh,
            train_chunk=self.chunk)
        try:
            with jax.set_mesh(mesh):
                report = loop.run(
                    start_step=None if spec.resume else 0)
        finally:
            if hasattr(feed, "close"):
                feed.close()
        if hasattr(feed, "stall_report"):
            report["feed"] = feed.stall_report()
        report["params"] = loop.state["params"]
        self._last_run = report
        return report


class CompiledServeSession(Program):
    """Request-level serving: slot pool + scheduler + compiled session cell.

    `open()` hands out a live `ServeSession`; `run()` is the one-shot path
    that fills every slot with one batch of requests, drains, and returns
    the tokens as one (B, 1+max_new) array (api.serve routes through
    this).
    """

    kind = "serve_session"

    def __init__(self, cluster, spec: ServeSessionProgram, policy):
        super().__init__(cluster, spec, policy)
        cfg = cluster._require_arch("ServeSessionProgram")
        if spec.admission not in ("fifo", "longest_prefix"):
            raise ValueError(f"unknown admission policy {spec.admission!r}")
        # raw (unjitted) per-slot-position decode step; the session chunk
        # jits the whole K-step program around it. Built once here so every
        # session opened on this program shares the compiled cell.
        step = steps.make_decode_step(cfg, max_seq=spec.max_seq,
                                      policy=policy)
        self._chunk_fn = engine.make_session_chunk(step, spec.chunk,
                                                   eos_id=spec.eos_id)
        if spec.paged:
            # shared paged KV pool: refill installs page tables, fault
            # programs route pool leaves by table (steps.py paged ops);
            # snapshot/restore stay None — preemption is off under paged
            pps = -((spec.max_seq + 1) // -spec.page_size)   # ceil
            self._pages_per_slot = pps
            self._n_pages = (spec.n_pages if spec.n_pages is not None
                             else spec.slots * pps + 1)      # +1: trash page
            ops = steps.make_paged_cache_ops(
                cfg, spec.slots, steps.decode_cache_len(cfg, spec.max_seq))
            self._refill_fn = engine.make_paged_session_refill(
                cache_zero=ops["zero_slots"])
            self._snapshot_fn = None
            self._restore_fn = None
            self._nan_scan_fn = engine.make_paged_nan_scan(ops["nan_slots"])
            self._corrupt_fn = engine.make_paged_slot_corrupt(
                ops["corrupt_slots"])
            self._page_copy_fn = engine.make_page_copy(ops["copy_pages"])
            self._page_scrub_fn = engine.make_page_scrub(ops["zero_pages"])
            # integrity programs: page readback feeds publish-time checksum
            # stamps + the background scrub; page flip is the scripted
            # silent-corruption fault (chaos only)
            self._page_read_fn = engine.make_page_read(ops["read_pages"])
            self._page_flip_fn = engine.make_page_flip(ops["flip_pages"])
        else:
            self._refill_fn = engine.make_session_refill(
                cache_zero=steps.zero_cache_slots)
            # checkpoint/restore + fault programs over the model cache
            # layout (stacked layer axes — the steps.py helpers know which
            # axis is batch per leaf; the engine defaults only cover flat
            # caches)
            self._snapshot_fn = engine.make_slot_snapshot(
                cache_take=steps.take_cache_slot)
            self._restore_fn = engine.make_slot_restore(
                cache_put=steps.put_cache_slot)
            self._nan_scan_fn = engine.make_nan_scan(
                cache_nan=steps.nan_cache_slots)
            self._corrupt_fn = engine.make_slot_corrupt(
                cache_fill=steps.fill_cache_slots)
            self._page_copy_fn = None
            self._page_scrub_fn = None
            self._page_read_fn = None
            self._page_flip_fn = None
        self._last_session = None

    def init_params(self, seed: int | None = None):
        cfg = self.cluster.arch
        seed = self.spec.seed if seed is None else seed
        return steps.init_params(cfg, jax.random.PRNGKey(seed),
                                 max_seq=self.spec.max_seq)

    def _make_state(self):
        cfg, spec = self.cluster.arch, self.spec
        clen = steps.decode_cache_len(cfg, spec.max_seq)
        if spec.paged:
            cache = steps.init_paged_cache(cfg, spec.slots, clen,
                                           n_pages=self._n_pages,
                                           page_size=spec.page_size)
            return engine.init_session_state(
                cache, spec.slots, spec.max_prompt,
                pages_per_slot=self._pages_per_slot)
        cache = steps.init_cache(cfg, spec.slots, clen)
        return engine.init_session_state(cache, spec.slots, spec.max_prompt)

    def open(self, params=None, faults=None, durable_dir=None,
             resume: bool = False, crash_hook=None,
             snapshot_every=None, journal_fsync=None,
             device=None, journal_group=None):
        """A fresh `ServeSession` over this compiled cell (own slot pool,
        queue, scheduler, and stall clock). `faults` arms a
        `runtime.FaultPlan` against the session (chaos testing).

        `durable_dir` turns on the durability layer: a crash-consistent
        request journal (fsync'd once per poll) plus, when the spec sets
        ``snapshot_every``, periodic bit-exact session snapshots.
        `resume=True` recovers from an existing `durable_dir` after a
        crash (see `restore()`). `snapshot_every` / `journal_fsync`
        override the spec's values per session — they are host-side
        knobs, so no recompile (`None` keeps the spec's choice).

        `device` pins the session's params and pool state to one device
        (the sharded session places each group on its own mesh slice):
        params drawn here and the pool are built on that device, never on
        the default one and copied; `journal_group` tags every journal
        event with the owning group id (see `runtime.Journal`). Both
        default to the single-session behaviour: default device, untagged
        journal."""
        from repro.runtime import ServeSession

        spec = self.spec
        make_state = self._make_state
        if device is not None:
            def on_device(build):
                with jax.default_device(device):
                    return jax.device_put(build(), device)

            params = (on_device(self.init_params) if params is None
                      else jax.device_put(params, device))
            make_state = lambda: on_device(self._make_state)
        elif params is None:
            params = self.init_params()
        kv = None
        if spec.paged:
            from repro.runtime.kvpool import PagedKV
            kv = PagedKV(self._n_pages, spec.page_size, spec.slots,
                         self._pages_per_slot,
                         prefix_cache=spec.prefix_cache,
                         recurrent=steps.recurrent_state(self.cluster.arch))
        sess = ServeSession(self._chunk_fn, self._refill_fn, params,
                            make_state(),
                            n_slots=spec.slots, chunk=spec.chunk,
                            max_prompt=spec.max_prompt, max_seq=spec.max_seq,
                            eos_id=spec.eos_id, max_queue=spec.max_queue,
                            admission=spec.admission,
                            shed_watermark=spec.shed_watermark,
                            aging_rounds=spec.aging_rounds,
                            preempt=spec.preempt and not spec.paged,
                            snapshot_fn=self._snapshot_fn,
                            restore_fn=self._restore_fn,
                            nan_scan_fn=self._nan_scan_fn,
                            corrupt_fn=self._corrupt_fn,
                            state_factory=make_state,
                            watchdog_s=spec.watchdog_s,
                            max_retries=spec.max_retries,
                            retry_backoff_s=spec.retry_backoff_s,
                            nan_check=spec.nan_check,
                            kv=kv,
                            page_copy_fn=self._page_copy_fn,
                            page_scrub_fn=self._page_scrub_fn,
                            faults=faults,
                            durable_dir=durable_dir,
                            snapshot_every=(spec.snapshot_every
                                            if snapshot_every is None
                                            else snapshot_every),
                            journal_fsync=(spec.journal_fsync
                                           if journal_fsync is None
                                           else journal_fsync),
                            page_read_fn=self._page_read_fn,
                            page_flip_fn=self._page_flip_fn,
                            scrub_pages=spec.scrub_pages,
                            crash_hook=crash_hook,
                            resume=resume,
                            journal_group=journal_group)
        self._last_session = sess
        return sess

    def restore(self, durable_dir, params=None, faults=None):
        """Resume a crashed session from its `durable_dir`: load the
        latest snapshot (if any), replay the journal tail, and hand back
        a live session. Requests that finished before the crash surface
        on `sess.recovered`; in-flight requests resume (bit-identically
        from the snapshot, or by re-prefill with the journal-committed
        prefix suppressed) — delivery stays exactly-once."""
        return self.open(params=params, faults=faults,
                         durable_dir=durable_dir, resume=True)

    def run(self, params=None, prompt=None, max_new: int | None = None) -> dict:
        """One-shot: submit one batch (one request per slot), drain, return
        `{"tokens": (B, 1+max_new), "stats": ...}`.

        Without `prompt`, slot i's request is the single start token 0,
        which sits in column 0. With `prompt` (B, P), the prompt is
        prefilled per slot and the first sampled token lands in column 0.
        Rows that stop early at `eos_id` are padded with it (with 0 when
        there is none). `stats` is the drained session's `stats()` plus
        `emitted_per_slot` (tokens after column 0) and, with an `eos_id`,
        `finished_slots`.
        """
        spec = self.spec
        max_new = spec.max_new if max_new is None else max_new
        sess = self.open(params=params)
        B = spec.slots
        if prompt is None:
            rows = [np.zeros(1, np.int32)] * B
            per_req = max_new
        else:
            prompt = np.asarray(prompt)
            rows = [prompt[i] for i in range(B)]
            # +1: the last prefill step's output is column 0
            per_req = max_new + 1
        handles = [sess.submit(r, per_req) for r in rows]
        stats = sess.drain()
        toks = [h.result() for h in handles]
        if prompt is None:
            toks = [np.concatenate([[0], t]).astype(np.int32) for t in toks]
        w = max(t.size for t in toks)
        pad = spec.eos_id if spec.eos_id is not None else 0
        out = np.full((B, w), pad, np.int32)
        for i, t in enumerate(toks):
            out[i, :t.size] = t
        stats["emitted_per_slot"] = [t.size - 1 for t in toks]
        if spec.eos_id is not None:
            stats["finished_slots"] = sum(h.hit_eos for h in handles)
        self._last_run = {"stats": stats, "tokens_shape": tuple(out.shape)}
        return {"tokens": out, "stats": stats}

    def report(self) -> dict:
        out = super().report()
        if self._last_session is not None:
            out["session"] = self._last_session.stats()
        return out


SHARD_MANIFEST = "manifest.json"
SHARD_MANIFEST_KIND = "repro-sharded-serve"


class CompiledShardedServeSession(CompiledServeSession):
    """N serving groups over one compiled session cell.

    The chunk/refill/fault programs are compiled once (inherited from
    `CompiledServeSession`); `open()` instantiates them `spec.groups`
    times — per-group params/state pinned to that group's device from
    `cells.group_devices` — and wires the cells behind a
    `runtime.ShardedServeSession` with a locality-aware `MeshScheduler`.

    Durable layout: the root directory holds a ``manifest.json``
    (`{"kind": "repro-sharded-serve", "version": 1, "groups": G}`) and
    one complete per-session durable dir per group (``group00/`` ...),
    each journal tagged with its group id. With ``groups=1`` the root
    directory *is* the group's durable dir — a plain
    `ServeSessionProgram` restore reads it unchanged, and a sharded
    restore accepts a plain session's manifest-less directory.
    """

    kind = "serve_session_sharded"

    def __init__(self, cluster, spec: ShardedServeSessionProgram, policy):
        if spec.groups < 1:
            raise ValueError(f"groups must be >= 1, got {spec.groups}")
        super().__init__(cluster, spec, policy)

    def _group_dirs(self, durable_dir, resume: bool) -> list:
        """Per-group durable dirs under the root, manifest-checked."""
        import json
        from pathlib import Path

        spec = self.spec
        root = Path(durable_dir)
        root.mkdir(parents=True, exist_ok=True)
        mpath = root / SHARD_MANIFEST
        if resume and mpath.exists():
            m = json.loads(mpath.read_text(encoding="utf-8"))
            if (m.get("kind") != SHARD_MANIFEST_KIND
                    or m.get("groups") != spec.groups):
                raise ValueError(
                    f"durable dir {root} was written by "
                    f"{m.get('kind')!r} with groups={m.get('groups')}; "
                    f"this program has groups={spec.groups}")
        elif resume and spec.groups != 1:
            # manifest-less dir: a plain single session wrote it; only a
            # 1-group sharded session can adopt it
            raise ValueError(
                f"durable dir {root} has no {SHARD_MANIFEST} — it holds a "
                f"single-session journal; restore it with groups=1 (or "
                f"ServeSessionProgram), not groups={spec.groups}")
        else:
            mpath.write_text(json.dumps(
                {"kind": SHARD_MANIFEST_KIND, "version": 1,
                 "groups": spec.groups}) + "\n", encoding="utf-8")
        if spec.groups == 1:
            return [root]
        return [root / f"group{g:02d}" for g in range(spec.groups)]

    def open(self, params=None, faults=None, durable_dir=None,
             resume: bool = False, crash_hook=None,
             snapshot_every=None, journal_fsync=None):
        """A live `runtime.ShardedServeSession`: `spec.groups` session
        cells, each on its own device slice, behind the single-session
        API. `faults` arms group 0 when given one `FaultPlan`, or each
        group when given a sequence (None entries skip a group)."""
        from repro.runtime.groups import (GroupPlan, GroupRuntime,
                                          MeshScheduler,
                                          ShardedServeSession)

        spec = self.spec
        G = spec.groups
        devices = cells.group_devices(self.cluster.mesh, G)
        # single distinct device (CPU smoke, groups=1): skip device_put so
        # the cell is bit-identical to the unsharded session's. Otherwise,
        # without `params`, each group draws its own (same seed) on its
        # own device, so no device holds a second copy
        distinct = len({id(d) for d in devices}) > 1
        if params is None and not distinct:
            params = self.init_params()
        plans = (list(faults) if isinstance(faults, (list, tuple))
                 else [faults] + [None] * (G - 1))
        if len(plans) != G:
            raise ValueError(f"faults: expected {G} plans, got {len(plans)}")
        dirs = (self._group_dirs(durable_dir, resume)
                if durable_dir is not None else [None] * G)
        groups = []
        for g in range(G):
            sess = super().open(
                params=params, faults=plans[g],
                durable_dir=(str(dirs[g]) if dirs[g] is not None else None),
                resume=resume, crash_hook=crash_hook,
                snapshot_every=snapshot_every,
                journal_fsync=journal_fsync,
                device=devices[g] if distinct else None,
                journal_group=g)
            groups.append(GroupRuntime(gid=g, session=sess,
                                       device=devices[g]))
        mesh = MeshScheduler(
            G, page_size=spec.page_size if spec.paged else 16)
        plan = GroupPlan(n_groups=G, devices=devices)
        sharded = ShardedServeSession(groups, mesh=mesh, plan=plan)
        self._last_session = sharded
        return sharded

    def run(self, params=None, prompt=None, max_new=None) -> dict:
        raise NotImplementedError(
            "the one-shot path is not defined for sharded sessions; use "
            "open() + submit/drain")


class CompiledDryRun(Program):
    kind = "dryrun"

    def run(self) -> dict:
        """Lower + compile the cell, extract memory/cost/collective analysis
        (the body of the old launch/dryrun.run_cell)."""
        from repro.core import hlo_cost, locality
        from repro.core import mesh as hw

        cluster, spec = self.cluster, self.spec
        cfg = cluster._require_arch("DryRunProgram")
        shape = SHAPES[spec.shape]
        ok, reason = cell_supported(cfg, shape)
        if not ok:
            record = {"status": "skipped", "reason": reason}
            self._last_run = record
            return record

        mesh, rules = cluster.mesh, cluster.rules
        with use_policy(self.policy):
            fn, args, in_sh, out_sh, donate = cells.build_cell(
                cfg, shape, mesh, rules, fsdp_gather=spec.fsdp_gather,
                policy=self.policy, decode_chunk=spec.decode_chunk,
                session=spec.session, paged=spec.paged,
                page_size=spec.page_size)
            t0 = time.time()
            with jax.set_mesh(mesh):
                lowered = jax.jit(fn, in_shardings=in_sh,
                                  out_shardings=out_sh,
                                  donate_argnums=donate).lower(*args)
                t_lower = time.time() - t0
                t0 = time.time()
                compiled = lowered.compile()
                t_compile = time.time() - t0

        mem = locality.extract_memory(compiled)
        ca = locality.extract_costs(compiled)
        print("memory_analysis:", compiled.memory_analysis())
        print("cost_analysis (built-in, loop-unaware):", ca)

        t0 = time.time()
        hlo_text = compiled.as_text()
        costs = hlo_cost.analyze(hlo_text)
        t_analyze = time.time() - t0

        n_chips = mesh.size
        mf = cells.model_flops(cfg, shape)
        flops_dev = costs["flops"]
        bytes_dev = costs["bytes"]
        coll_dev = costs["collective_operand_bytes"]
        wire_dev = costs["collective_wire_bytes"]
        record = {
            "status": "ok",
            "n_chips": n_chips,
            "seconds": {"lower": t_lower, "compile": t_compile,
                        "analyze": t_analyze},
            "memory_analysis": mem,
            "peak_device_bytes": locality.peak_device_bytes(mem),
            "cost_analysis_builtin": ca,
            "hlo": {
                "flops_per_device": flops_dev,
                "bytes_per_device": bytes_dev,
                "transcendentals_per_device": costs["transcendentals"],
                "collective_operand_bytes_per_device": coll_dev,
                "collective_wire_bytes_per_device": wire_dev,
                "collectives": costs["collectives"],
            },
            "model": mf,
            "roofline": {
                # terms in seconds, per the task's definitions
                "compute_s": flops_dev * n_chips / (
                    n_chips * hw.PEAK_FLOPS_BF16),
                "memory_s": bytes_dev * n_chips / (n_chips * hw.HBM_BW),
                "collective_s": coll_dev * n_chips / (
                    n_chips * hw.ICI_BW_PER_LINK),
                "collective_wire3_s": wire_dev / (3 * hw.ICI_BW_PER_LINK),
                "useful_flops_ratio": mf["model_flops"] / max(
                    flops_dev * n_chips, 1.0),
            },
        }
        dom = max(("compute_s", "memory_s", "collective_s"),
                  key=lambda k: record["roofline"][k])
        record["roofline"]["dominant"] = dom
        self._last_run = record
        return record


class CompiledBench(Program):
    kind = "bench"

    def run(self, modules, echo=print) -> dict:
        """Run the offered bench `modules` ([(name, module)]) under this
        program's policy. Each section's CSV rows are echoed as they land
        and collected (with per-row median over `repeat` runs); the active
        policy — knobs plus tune-hit counters — rides in the result."""
        import sys
        import traceback

        spec = self.spec
        wanted = set(spec.sections) if spec.sections else None
        results: dict = {"smoke": spec.smoke, "sections": {}}
        failed = []
        with use_policy(self.policy) as pol:
            for name, mod in modules:
                if wanted is not None and name not in wanted:
                    continue
                t0 = time.perf_counter()
                try:
                    lines = _median_lines(
                        [_call_main(mod, spec.smoke)
                         for _ in range(spec.repeat)])
                    for line in lines:
                        echo(line)
                    results["sections"][name] = {
                        "status": "ok",
                        "seconds": time.perf_counter() - t0,
                        "rows": [_parse_row(line) for line in lines],
                    }
                except Exception as e:
                    failed.append(name)
                    traceback.print_exc()
                    results["sections"][name] = {
                        "status": "error",
                        "seconds": time.perf_counter() - t0,
                        "error": f"{type(e).__name__}: {e}",
                    }
                print(f"# {name} done in {time.perf_counter() - t0:.1f}s",
                      file=sys.stderr)
        results["policy"] = pol.describe()
        results["failed"] = failed
        self._last_run = {"failed": failed,
                          "sections": sorted(results["sections"])}
        return results


def _call_main(mod, smoke: bool) -> list[str]:
    import inspect
    if "smoke" in inspect.signature(mod.main).parameters:
        return mod.main(smoke=smoke)
    return mod.main()


def _parse_row(line: str) -> dict:
    name, us, derived = line.split(",", 2)
    try:
        us_val = float(us)
    except ValueError:
        us_val = None
    return {"name": name, "us_per_call": us_val, "derived": derived}


def _median_lines(runs: list[list[str]]) -> list[str]:
    """Per-row median us_per_call across repeats (first run's derived)."""
    import statistics
    if len(runs) == 1:
        return runs[0]
    by_name: dict[str, list[float]] = {}
    for run in runs:
        for line in run:
            r = _parse_row(line)
            if r["us_per_call"] is not None:
                by_name.setdefault(r["name"], []).append(r["us_per_call"])
    out = []
    for line in runs[0]:
        r = _parse_row(line)
        if r["us_per_call"] is None or r["name"] not in by_name:
            out.append(line)
            continue
        med = statistics.median(by_name[r["name"]])
        out.append(f"{r['name']},{med:.1f},{r['derived']}")
    return out
