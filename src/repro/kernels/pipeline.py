"""Unified tile-pipeline layer — one memory hierarchy, many kernels.

MemPool's claim is that a single hierarchical fabric (tile -> group ->
cluster, hybrid local/interleaved addressing, double-buffered DMA) serves
every kernel. This module is that claim as code for the TPU translation:
every Pallas kernel in this repo describes itself as

  * a set of `TileSpec`s — block shapes + index maps, i.e. which slice of
    each operand is resident in VMEM ("the local tile") at each grid step;
  * a tuple of `GridAxis`es — the iteration space with per-dimension
    semantics ("parallel" = independent tiles, "arbitrary" = sequential,
    carrying VMEM scratch across steps — the paper's sequential region);
  * optional VMEM scratch — the "register tile" held across the sequential
    axis (matmul accumulator, flash-attention online-softmax state);

and `KernelPipeline` emits the `pl.pallas_call`. Pallas's grid pipeline
double-buffers every streamed operand block (the DMA of block k+1 rides
under the compute of block k — paper Fig. 15 / TCDM burst streaming), which
is why `vmem_bytes()` charges two slots per streamed tile and why the cost
model overlaps the memory and compute terms with `max()`.

The autotuner (`autotune`) *ranks* block-size candidates by scoring each
against the repo's existing cost models: `launch/roofline.kernel_roofline`
for the compute/memory terms and `core/interconnect.TopologyModel` for the
locality penalty — candidates that re-stream operands (low reuse = low
p_local in MemPool terms) pay the congested-fabric latency blow-up of the
paper's Fig. 5 model. The *pick*, however, is measured, not modeled: the
top-N modeled candidates plus the hand-picked default are compiled and
raced on device (warmup + median-of-repeats wall time — the same timing
loop the benchmark driver uses), and the measured winner is kept. The
score only prunes the search space; it proved unable to discriminate
between valid blockings (every record used to report modeled_speedup=1.00
while several "tuned" picks were measurably slower than the defaults).
Winning records are registered in `configs/registry.KERNEL_TUNES` — and
written through to the active `kernels.tunedb.TuneDB` — so launchers,
benchmarks, and later processes share one measurement.
"""

from __future__ import annotations

import dataclasses
import math
import os
import statistics
import time
from typing import Any, Callable, Iterator, Sequence

import jax
from jax.experimental import pallas as pl

from repro.core import mesh as hw
from repro.core.interconnect import TOP_H, TopologyModel
from repro.launch.roofline import kernel_roofline

# ----------------------------------------------------------------------------
# Tile / grid description
# ----------------------------------------------------------------------------

def _memory_space(name: str):
    from jax.experimental.pallas import tpu as pltpu
    return {"smem": pltpu.SMEM}[name]


@dataclasses.dataclass(frozen=True)
class TileSpec:
    """One operand's residency: the VMEM block and where it comes from.

    `block` is the tile held on-chip per grid step (the paper's per-core
    working set); `index_map` routes grid coordinates to block coordinates —
    including neighbor/halo routing (conv2d) and head-group folding
    (flash-attention GQA), the analogue of the hybrid addressing scheme's
    scrambler. `memory_space="smem"` marks scalar operands.
    """

    block: tuple[int, ...]
    index_map: Callable[..., tuple] | None = None
    memory_space: str | None = None           # None -> pipelined VMEM

    def block_spec(self) -> pl.BlockSpec:
        if self.memory_space is None:
            return pl.BlockSpec(self.block, self.index_map)
        return pl.BlockSpec(self.block, self.index_map,
                            memory_space=_memory_space(self.memory_space))

    def bytes_per_step(self, dtype_bytes: int) -> int:
        return math.prod(self.block) * dtype_bytes


@dataclasses.dataclass(frozen=True)
class GridAxis:
    """One grid dimension with its MemPool-flavoured semantics.

    "parallel"  — tiles are independent (cores race ahead);
    "arbitrary" — sequential on TPU: VMEM scratch carries across steps,
                  the paper's sequential region owned by one tile.
    """

    name: str
    size: int
    semantics: str = "parallel"

    def __post_init__(self):
        assert self.semantics in ("parallel", "arbitrary"), self.semantics
        assert self.size >= 1, (self.name, self.size)


# ----------------------------------------------------------------------------
# Fusion hooks — prologue on streamed input tiles, epilogue before writeback
# ----------------------------------------------------------------------------
#
# MemPool's DMA engine exists so intermediate tiles are *consumed in L1*
# instead of bouncing through higher memory. The TPU translation: a producer
# kernel's body is stitched into the consumer's grid either as a *prologue*
# (applied to a streamed operand tile right after it lands in VMEM — e.g.
# rmsnorm folded onto the matmul A tile) or an *epilogue* (applied to the
# register/output tile right before writeback — e.g. bias + GELU after the
# K loop). Both run on tile *values*; the hook machinery below intercepts
# ref loads/stores so existing kernel bodies compose unchanged.


@dataclasses.dataclass(frozen=True)
class _Hook:
    """One fusion hook bound to its own slice of the extra-tile operands.

    Each fuse() call appends its extra tiles and binds its hooks to exactly
    that range, so stacked fusions never see each other's operands.
    """

    fn: Callable
    extras_range: tuple[int, int]       # half-open range into extra_tiles

    def __call__(self, value, extras: tuple):
        lo, hi = self.extras_range
        return self.fn(value, *extras[lo:hi])


class _PrologueRef:
    """Wraps an input ref; loads run through the hook chain in fuse order."""

    def __init__(self, ref, hooks: Sequence[_Hook], extras: tuple):
        self._ref = ref
        self._hooks = tuple(hooks)
        self._extras = extras

    def __getitem__(self, idx):
        value = self._ref[idx]
        for hook in self._hooks:
            value = hook(value, self._extras)
        return value

    def __getattr__(self, name):
        return getattr(self._ref, name)


class _EpilogueRef:
    """Wraps an output ref; stores run through the hook chain (innermost —
    most recently fused — first).

    Each hook sees the value the body (or the previous hook) produced and
    returns the fused result; the wrapper re-casts at the end so hooks are
    free to compute in f32.
    """

    def __init__(self, ref, hooks: Sequence[_Hook], extras: tuple):
        self._ref = ref
        self._hooks = tuple(hooks)
        self._extras = extras

    def __setitem__(self, idx, value):
        for hook in self._hooks:
            value = hook(value, self._extras)
        self._ref[idx] = value.astype(self._ref.dtype)

    def __getitem__(self, idx):
        return self._ref[idx]

    def __getattr__(self, name):
        return getattr(self._ref, name)


class FusionError(ValueError):
    """Raised when producer/consumer TileSpecs cannot be stitched."""


def check_fusable(producer_tile: TileSpec, consumer_tile: TileSpec,
                  *, full_dims: Sequence[int] = (),
                  dims: Sequence[int] = ()) -> None:
    """Validate that a producer's output tile can feed a consumer's input.

    Same residency (both pipelined VMEM or both SMEM) and identical block
    shape — the producer tile must be *fully consumed* in the step that
    loads it, or the fusion would recompute partial tiles inconsistently.
    `full_dims` lists block axes that must span the whole array dimension
    (given through `dims`), e.g. a row-normalization folded into a matmul
    prologue needs the entire reduction dim resident per tile.
    """
    if producer_tile.memory_space != consumer_tile.memory_space:
        raise FusionError(
            f"residency mismatch: producer {producer_tile.memory_space} vs "
            f"consumer {consumer_tile.memory_space}")
    if tuple(producer_tile.block) != tuple(consumer_tile.block):
        raise FusionError(
            f"tile shape mismatch: producer {producer_tile.block} vs "
            f"consumer {consumer_tile.block}; the producer tile must be "
            f"fully consumed per grid step")
    for axis, dim in zip(full_dims, dims):
        if consumer_tile.block[axis] != dim:
            raise FusionError(
                f"block axis {axis} covers {consumer_tile.block[axis]} of "
                f"{dim}; the fused producer needs the full dimension "
                f"resident per tile")


class KernelPipeline:
    """Builds one `pl.pallas_call` from tiles + grid + register-tile scratch.

    `prologues` maps input-operand index -> hook applied to that operand's
    tile on load; `epilogue` is applied to every output-tile store.
    `extra_tiles` are additional operands (scales, biases, residual tiles)
    consumed only by the hooks; they are appended after `in_tiles` in the
    emitted pallas_call's operand order.
    """

    def __init__(self, name: str, body: Callable, grid: Sequence[GridAxis],
                 in_tiles: Sequence[TileSpec],
                 out_tiles: TileSpec | Sequence[TileSpec],
                 out_shape: Any, scratch: Sequence[Any] = (),
                 cost: "Traffic | None" = None,
                 prologues: dict[int, Callable] | None = None,
                 epilogue: Callable | None = None,
                 extra_tiles: Sequence[TileSpec] = ()):
        self.name = name
        self.body = body
        self.grid = tuple(grid)
        self.in_tiles = tuple(in_tiles)
        self.out_tiles = (tuple(out_tiles) if isinstance(out_tiles, (tuple, list))
                          else (out_tiles,))
        self.multi_out = isinstance(out_tiles, (tuple, list))
        self.out_shape = out_shape
        self.scratch = tuple(scratch)
        self.cost = cost
        self.extra_tiles = tuple(extra_tiles)
        whole = (0, len(self.extra_tiles))
        self._pro_hooks: dict[int, list[_Hook]] = {
            idx: [_Hook(fn, whole)] for idx, fn in (prologues or {}).items()}
        self._epi_hooks: list[_Hook] = \
            [_Hook(epilogue, whole)] if epilogue is not None else []
        for idx in self._pro_hooks:
            if not 0 <= idx < len(self.in_tiles):
                raise FusionError(f"prologue on operand {idx}, but pipeline "
                                  f"has {len(self.in_tiles)} inputs")

    def fuse(self, *, prologues: dict[int, Callable] | None = None,
             epilogue: Callable | None = None,
             extra_tiles: Sequence[TileSpec] = (),
             name: str | None = None,
             cost: "Traffic | None" = None) -> "KernelPipeline":
        """Return a new pipeline with producer/consumer hooks stitched in.

        The new fusion's extra tiles are appended and its hooks are bound
        to exactly that slice, so stacked fusions compose without seeing
        each other's operands. Prologue indices refer to the *core* operand
        order; an existing hook on the same slot composes (new prologue
        runs after the old one; new epilogue before the old one, i.e.
        closest to the register tile first).
        """
        fused = KernelPipeline(
            name=name or self.name, body=self.body, grid=self.grid,
            in_tiles=self.in_tiles, out_tiles=(
                tuple(self.out_tiles) if self.multi_out else self.out_tiles[0]),
            out_shape=self.out_shape, scratch=self.scratch,
            cost=cost if cost is not None else self.cost,
            extra_tiles=(*self.extra_tiles, *extra_tiles))
        fused._pro_hooks = {idx: list(hooks)
                            for idx, hooks in self._pro_hooks.items()}
        fused._epi_hooks = list(self._epi_hooks)
        rng = (len(self.extra_tiles),
               len(self.extra_tiles) + len(extra_tiles))
        for idx, fn in (prologues or {}).items():
            if not 0 <= idx < len(self.in_tiles):
                raise FusionError(f"prologue on operand {idx}, but pipeline "
                                  f"has {len(self.in_tiles)} inputs")
            fused._pro_hooks.setdefault(idx, []).append(_Hook(fn, rng))
        if epilogue is not None:
            fused._epi_hooks.insert(0, _Hook(epilogue, rng))
        return fused

    # -- introspection -------------------------------------------------------
    @property
    def grid_steps(self) -> int:
        return math.prod(a.size for a in self.grid)

    def dimension_semantics(self) -> tuple[str, ...]:
        return tuple(a.semantics for a in self.grid)

    def vmem_bytes(self, dtype_bytes: int = 4) -> int:
        """Double-buffered VMEM footprint: 2 slots per streamed tile (the
        pipeline's in-flight copy of block k+1 next to block k) + scratch.

        Introspection for a *built* pipeline. The autotuner budget-checks the
        per-kernel `traffic()` formulas instead (pure shape math, no pipeline
        construction per candidate); those may under-count resident constant
        tiles deliberately (e.g. conv2d's 3x3 weight is charged once).
        """
        tiles = [t for t in (*self.in_tiles, *self.extra_tiles,
                             *self.out_tiles)
                 if t.memory_space is None]
        streamed = 2 * sum(t.bytes_per_step(dtype_bytes) for t in tiles)
        scratch = 0
        for s in self.scratch:
            shape = getattr(s, "shape", None)
            dt = getattr(s, "dtype", None)
            if shape is not None:
                scratch += math.prod(shape) * (
                    jax.numpy.dtype(dt).itemsize if dt is not None else 4)
        return streamed + scratch

    # -- emission ------------------------------------------------------------
    def _hooked_body(self) -> Callable:
        """Wrap `body` so hook-bearing refs apply prologues/epilogue.

        The emitted kernel receives (core inputs, extra tiles, outputs,
        scratch); the original body still sees only (core inputs, outputs,
        scratch) — fusion operands exist purely for the hooks.
        """
        if not (self._pro_hooks or self._epi_hooks or self.extra_tiles):
            return self.body
        n_in = len(self.in_tiles)
        n_extra = len(self.extra_tiles)
        n_out = len(self.out_tiles)

        def wrapped(*refs):
            core = list(refs[:n_in])
            extras = tuple(refs[n_in:n_in + n_extra])
            outs = list(refs[n_in + n_extra:n_in + n_extra + n_out])
            scratch = refs[n_in + n_extra + n_out:]
            for idx, hooks in self._pro_hooks.items():
                core[idx] = _PrologueRef(core[idx], hooks, extras)
            if self._epi_hooks:
                outs = [_EpilogueRef(o, self._epi_hooks, extras)
                        for o in outs]
            return self.body(*core, *outs, *scratch)

        return wrapped

    def pallas_call(self, *, interpret: bool = False) -> Callable:
        from jax.experimental.pallas import tpu as pltpu
        out_specs = tuple(t.block_spec() for t in self.out_tiles)
        cost = (pl.CostEstimate(flops=int(self.cost.flops),
                                bytes_accessed=int(self.cost.hbm_bytes),
                                transcendentals=int(self.cost.transcendentals))
                if self.cost is not None else None)
        # compiled under the same VMEM limit the autotuner budgets against,
        # so a blocking it admits is one the compiler accepts
        params = pltpu.CompilerParams(
            dimension_semantics=self.dimension_semantics(),
            vmem_limit_bytes=VMEM_BUDGET_BYTES)
        return pl.pallas_call(
            self._hooked_body(),
            grid=tuple(a.size for a in self.grid),
            in_specs=[t.block_spec()
                      for t in (*self.in_tiles, *self.extra_tiles)],
            out_specs=out_specs if self.multi_out else out_specs[0],
            out_shape=self.out_shape,
            scratch_shapes=list(self.scratch),
            compiler_params=params,
            cost_estimate=cost,
            interpret=interpret)

    def __call__(self, *operands, interpret: bool = False):
        return self.pallas_call(interpret=interpret)(*operands)


# ----------------------------------------------------------------------------
# Traffic / cost model
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Traffic:
    """Structural traffic of one kernel invocation under a given blocking.

    `saved_bytes` is only set on fused kernels: the intermediate's write +
    read that the unfused producer/consumer composition would have streamed
    through HBM and the fusion eliminates. The unfused composition's traffic
    is therefore `hbm_bytes + saved_bytes` (plus the producer's own operand
    reads, which both paths share).
    """

    flops: float
    hbm_bytes: float        # streamed under this blocking (re-fetches counted)
    ideal_bytes: float      # compulsory traffic: every operand/result once
    grid_steps: int
    vmem_bytes: int
    transcendentals: float = 0.0
    saved_bytes: float = 0.0


def fused_traffic(consumer: Traffic, producer: Traffic,
                  intermediate_bytes: float, *,
                  extra_vmem: int = 0, refetch: int = 1) -> Traffic:
    """Traffic of a producer fused into a consumer's grid.

    The producer's compute rides along (re-run `refetch` times when the
    consumer re-streams the fused operand — e.g. a norm prologue recomputes
    once per N-block column); the intermediate's HBM write (producer side)
    and read (consumer side) disappear. `intermediate_bytes` is the size of
    that intermediate counted once.
    """
    saved = 2.0 * intermediate_bytes
    return Traffic(
        flops=consumer.flops + producer.flops * refetch,
        hbm_bytes=consumer.hbm_bytes + producer.hbm_bytes - saved,
        ideal_bytes=consumer.ideal_bytes + producer.ideal_bytes - saved,
        grid_steps=consumer.grid_steps,
        vmem_bytes=consumer.vmem_bytes + extra_vmem,
        transcendentals=(consumer.transcendentals
                         + producer.transcendentals * refetch),
        saved_bytes=saved,
    )


# fixed per-grid-step pipeline bookkeeping (index computation, DMA issue);
# penalizes degenerate tiny tiles the roofline terms alone would not
GRID_STEP_SECONDS = 2e-7
# injected load at which the locality penalty is evaluated (a busy fabric,
# below the Top_H saturation point — paper Fig. 5 operating point)
_INJECTED_LOAD = 0.3


def locality_factor(traffic: Traffic,
                    model: TopologyModel | None = None) -> tuple[float, float]:
    """(latency blow-up >= 1, p_local) for this blocking's reuse behaviour.

    Reuse fraction = compulsory / streamed bytes: every re-streamed byte is
    a "remote" access in MemPool terms, every reused byte a local-tile hit.
    The Top_H congestion model turns that into an average-latency ratio
    versus the perfectly-local schedule.
    """
    model = model or TopologyModel(TOP_H)
    p_local = min(1.0, traffic.ideal_bytes / max(traffic.hbm_bytes, 1.0))
    base = model.avg_latency(_INJECTED_LOAD, p_local=1.0)
    factor = model.avg_latency(_INJECTED_LOAD, p_local=p_local) / base
    return max(factor, 1.0), p_local


@dataclasses.dataclass(frozen=True)
class CostBreakdown:
    compute_s: float
    memory_s: float
    overhead_s: float
    locality: float
    p_local: float
    total_s: float


def score(traffic: Traffic, model: TopologyModel | None = None) -> CostBreakdown:
    """Modeled seconds for one invocation: double-buffered overlap of the
    roofline compute/memory terms, memory scaled by the interconnect-model
    locality penalty, plus per-step pipeline overhead."""
    r = kernel_roofline(traffic.flops, traffic.hbm_bytes)
    factor, p_local = locality_factor(traffic, model)
    memory_s = r["memory_s"] * factor
    overhead = traffic.grid_steps * GRID_STEP_SECONDS
    total = max(r["compute_s"], memory_s) + overhead
    return CostBreakdown(compute_s=r["compute_s"], memory_s=memory_s,
                         overhead_s=overhead, locality=factor,
                         p_local=p_local, total_s=total)


# ----------------------------------------------------------------------------
# Kernel registry
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelDef:
    """A kernel's contract with the pipeline layer.

    `traffic(shapes, blocks, dtype_bytes)` and `tune_space(shapes)` are pure
    shape math — the autotuner never runs the kernel.
    """

    name: str
    traffic: Callable[[dict, dict, int], Traffic]
    tune_space: Callable[[dict], Iterator[dict]]
    default_blocks: Callable[[dict], dict]


KERNELS: dict[str, KernelDef] = {}


def register(defn: KernelDef) -> KernelDef:
    KERNELS[defn.name] = defn
    return defn


def shape_key(shapes: dict, dtype_bytes: int = 4) -> str:
    # dtype_bytes is part of the key: blocks tuned under a 2-byte VMEM
    # footprint are not valid for 4-byte operands of the same shape
    return f"b{dtype_bytes}_" + "_".join(
        f"{k}{shapes[k]}" for k in sorted(shapes))


def block_candidates(dim: int, *, align: int = 8, cap: int = 8,
                     max_block: int | None = None) -> list[int]:
    """Divisors of `dim` that are multiples of `align`, geometrically thinned.

    Falls back to [dim] when nothing aligns (tiny dims) so every kernel
    always has at least one valid, divisibility-respecting candidate.
    """
    cands = [d for d in range(align, dim + 1, align) if dim % d == 0]
    if not cands:
        cands = [dim]
    if max_block is not None:
        capped = [c for c in cands if c <= max_block]
        cands = capped or [min(cands)]
    if len(cands) > cap:
        idx = sorted({round(i * (len(cands) - 1) / (cap - 1))
                      for i in range(cap)})
        cands = [cands[i] for i in idx]
    return cands


def snap_block(dim: int, block: int) -> int:
    """Largest divisor of `dim` that is <= `block` (>= 1)."""
    block = max(1, min(block, dim))
    while dim % block:
        block -= 1
    return block


def resolve_block(dim: int, block: int | None, default: int) -> int:
    """Resolve one block size against its dimension.

    `None` (the wrapper default) snaps `default` to the largest divisor, so
    any operand shape works out of the box. An explicit value is capped at
    the dimension itself (a block can't exceed the array; the cap is the
    whole-dim block, exactly divisible) and must then divide — silently
    substituting some *smaller* blocking for one the caller asked for would
    invalidate their benchmark, so non-divisors raise instead.
    """
    if block is None:
        return snap_block(dim, default)
    block = max(1, min(block, dim))
    if dim % block:
        raise ValueError(
            f"block size {block} does not divide dimension {dim}; pass a "
            f"divisor or omit it for the snapped default")
    return block


def mxu_align(dim: int) -> int:
    """MXU-facing dims prefer 128-aligned tiles; fall back for small dims."""
    return hw.MXU_TILE if dim % hw.MXU_TILE == 0 else 8


# ----------------------------------------------------------------------------
# Autotuner
# ----------------------------------------------------------------------------

# The scoped VMEM limit every kernel is compiled under (`vmem_limit_bytes`)
# and the budget the autotuner admits blockings against: one number, so a
# modeled pick is never one the compiler refuses. Half the physical VMEM
# leaves the rest to XLA's own fusions around the kernel. (Left unset, the
# limit would be the compiler's default scoped limit, 16 MiB on v5e.)
VMEM_BUDGET_BYTES = hw.VMEM_BYTES // 2


@dataclasses.dataclass(frozen=True)
class TuneResult:
    kernel: str
    shapes: tuple[tuple[str, int], ...]
    blocks: dict[str, int]
    cost: CostBreakdown
    default_blocks: dict[str, int]
    default_cost: CostBreakdown
    # timed-race results; 0.0 / "modeled" when the pick was score-only
    # (frozen mode, or no operand factory)
    measured_us: float = 0.0
    default_us: float = 0.0
    source: str = "modeled"
    raced: int = 0                  # lanes actually timed (incl. default)
    # "fused": the kernel body won (with `blocks`); "unfused": the op's
    # composition of primitive kernels beat every blocking, and tuned_call
    # dispatches the composition for this (kernel, shapes) cell instead
    route: str = "fused"
    # race lanes that failed to compile or run: "<kernel> <blocks>:
    # <error type>: <first line of the error>", one per lane
    refused: tuple[str, ...] = ()

    @property
    def timed(self) -> bool:
        return self.measured_us > 0.0

    @property
    def measured_speedup(self) -> float:
        """Raced wall-time speedup over the default blocking; >= 1.0 by
        construction (the default is always a race lane), 1.0 untimed."""
        if not self.timed:
            return 1.0
        return self.default_us / max(self.measured_us, 1e-30)


# -- the timing loop ---------------------------------------------------------
# Shared with the benchmark driver (benchmarks/bench_table1_kernels.timeit
# delegates here): warmup runs absorb compilation, then the median of
# `reps` blocked wall-clock runs. Medians, not means — one GC pause or
# compile-cache refill must not hand the race to the wrong blocking.

def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    return int(raw) if raw else default


def median_time(fn: Callable[[], Any], *, reps: int = 3,
                warmup: int = 1) -> float:
    """Median wall seconds per call of `fn()` after `warmup` discarded runs."""
    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn())
    times = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


@dataclasses.dataclass(frozen=True)
class _RaceOutcome:
    blocks: dict[str, int]
    measured_s: float
    default_s: float
    lanes: int
    route: str = "fused"
    refused: tuple[str, ...] = ()


class RaceFailed(RuntimeError):
    """Every lane of a timed race failed to compile or run."""


def _refusal(kernel: str, blocks: dict, err: Exception) -> str:
    """One refused lane, reported: the policy's ``tune_lane_failures``
    counter and the first line of the error, tagged with the lane."""
    from repro.cluster.policy import current_policy
    current_policy().bump("tune_lane_failures")
    first = (str(err).strip().splitlines() or [type(err).__name__])[0]
    return f"{kernel} {blocks}: {type(err).__name__}: {first}"


# sentinel "blocks" dict the composition lane hands the injectable timer —
# tests key on it to force the unfused route to win or lose a race
COMPOSITION_LANE = {"route": "unfused"}


def _race_dtype(dtype_bytes: int):
    return {2: jax.numpy.bfloat16, 8: jax.numpy.float64}.get(
        dtype_bytes, jax.numpy.float32)


def _race(kernel: str, shapes: dict, candidates: Sequence[dict],
          default_blocks: dict, dtype_bytes: int, *,
          timer: Callable[[Callable, dict], float] | None = None,
          reps: int | None = None,
          warmup: int | None = None) -> _RaceOutcome | None:
    """Time each candidate blocking (plus the default) on device and return
    the measured winner; None when racing is impossible (no operand
    factory for this kernel, or operand synthesis failed) — the caller
    keeps the modeled pick. A lane that fails to compile or run cannot
    win; each such lane is reported (`_refusal`), and when every kernel
    lane fails the race raises `RaceFailed` instead of handing back a
    blocking nobody has seen run.

    When the descriptor carries an unfused `composition`, it races as one
    extra lane (timed with the `COMPOSITION_LANE` sentinel as its blocks
    dict). If it beats every kernel blocking the outcome's route flips to
    "unfused" — `blocks` still records the best *kernel* blocking so the
    record stays usable if the composition is ever unavailable.

    `timer(fn, blocks) -> seconds` is injectable for deterministic tests;
    the default is `median_time` with REPRO_TUNE_REPS/1-warmup settings.
    Operands are *synthesized* from the shape dict (never taken from the
    calling site — tuned_call may be running under a jit trace where the
    real operands are tracers).
    """
    from repro.kernels import ops
    desc = ops.OPS.get(kernel)
    if desc is None or desc.operands is None:
        return None
    try:
        operands = desc.operands(shapes, _race_dtype(dtype_bytes))
    except Exception:
        return None
    if timer is None:
        reps = _env_int("REPRO_TUNE_REPS", 3) if reps is None else reps
        warmup = 1 if warmup is None else warmup

        def timer(fn, blocks, _r=reps, _w=warmup):
            return median_time(fn, reps=_r, warmup=_w)

    lanes: list[dict] = []
    seen: set = set()
    for b in (*candidates, dict(default_blocks)):
        k = tuple(sorted(b.items()))
        if k not in seen:
            seen.add(k)
            lanes.append(dict(b))
    times: list[float] = []
    refused: list[str] = []
    for b in lanes:
        try:
            times.append(float(timer(lambda b=b: desc.wrapper(*operands, **b),
                                     b)))
        except Exception as e:              # a lane that won't run can't win
            times.append(float("inf"))
            refused.append(_refusal(kernel, b, e))
    best = min(range(len(lanes)), key=times.__getitem__)
    if not math.isfinite(times[best]):
        raise RaceFailed(f"every lane of the {kernel} race at {shapes} "
                         f"failed:\n  " + "\n  ".join(refused))
    default_key = tuple(sorted(default_blocks.items()))
    default_s = next(t for b, t in zip(lanes, times)
                     if tuple(sorted(b.items())) == default_key)
    comp_s, comp_lanes = float("inf"), 0
    if desc.composition is not None:
        comp_lanes = 1
        try:
            comp_s = float(timer(lambda: desc.composition(*operands),
                                 dict(COMPOSITION_LANE)))
        except Exception as e:
            comp_s = float("inf")
            refused.append(_refusal(kernel, dict(COMPOSITION_LANE), e))
    if comp_s < times[best]:
        return _RaceOutcome(blocks=lanes[best], measured_s=comp_s,
                            default_s=default_s,
                            lanes=len(lanes) + comp_lanes, route="unfused",
                            refused=tuple(refused))
    return _RaceOutcome(blocks=lanes[best], measured_s=times[best],
                        default_s=default_s, lanes=len(lanes) + comp_lanes,
                        refused=tuple(refused))


def autotune(kernel: str, shapes: dict, *, dtype_bytes: int = 4,
             vmem_budget: int = VMEM_BUDGET_BYTES,
             register_record: bool = True,
             mode: str | None = None,
             timer: Callable[[Callable, dict], float] | None = None,
             top_n: int | None = None,
             reps: int | None = None) -> TuneResult:
    """Pick the measured-fastest valid blocking for `kernel` at `shapes`.

    Every candidate from the kernel's tune space is checked for
    divisibility (the space only emits divisors) and the double-buffered
    VMEM budget, then *ranked* with the modeled `score`. Under the "timed"
    tune mode (the default — see `kernels.tunedb.tune_mode`), the top
    `top_n` (REPRO_TUNE_TOPN, default 3) modeled candidates and the
    hand-picked default are then compiled and raced with warmup +
    median-of-repeats timing, and the measured winner is kept; "modeled"
    keeps the score-only pick (the legacy behaviour), and "frozen" does
    the same while guaranteeing no DB write (CI determinism). The winner
    is recorded in `configs.registry.KERNEL_TUNES` keyed on (kernel,
    shape_key) and — for timed picks — written through to the active
    TuneDB. One race bumps the ambient KernelPolicy's `tune_races`
    counter.
    """
    from repro.kernels import tunedb

    defn = KERNELS[kernel]
    scored: list[tuple[float, dict]] = []
    for blocks in defn.tune_space(shapes):
        t = defn.traffic(shapes, blocks, dtype_bytes)
        if t.vmem_bytes > vmem_budget:
            continue
        scored.append((score(t).total_s, dict(blocks)))
    if not scored:
        raise ValueError(
            f"no {kernel} blocking at {shapes} fits the "
            f"{vmem_budget / 2**20:.1f} MiB VMEM budget")
    scored.sort(key=lambda sc: sc[0])
    best_blocks = dict(scored[0][1])
    default = defn.default_blocks(shapes)
    default_cost = score(defn.traffic(shapes, default, dtype_bytes))

    resolved = tunedb.tune_mode(mode)
    measured_us = default_us = 0.0
    source, raced, route, refused = "modeled", 0, "fused", ()
    if resolved == "timed":
        top_n = _env_int("REPRO_TUNE_TOPN", 3) if top_n is None else top_n
        outcome = _race(kernel, shapes,
                        [b for _, b in scored[:max(top_n, 1)]], default,
                        dtype_bytes, timer=timer, reps=reps)
        if outcome is not None:
            best_blocks = dict(outcome.blocks)
            measured_us = outcome.measured_s * 1e6
            default_us = outcome.default_s * 1e6
            source, raced, route = "timed", outcome.lanes, outcome.route
            refused = outcome.refused
            from repro.cluster.policy import current_policy
            current_policy().bump("tune_races")

    best_cost = score(defn.traffic(shapes, best_blocks, dtype_bytes))
    result = TuneResult(kernel=kernel,
                        shapes=tuple(sorted(shapes.items())),
                        blocks=best_blocks, cost=best_cost,
                        default_blocks=dict(default),
                        default_cost=default_cost,
                        measured_us=measured_us, default_us=default_us,
                        source=source, raced=raced, route=route,
                        refused=refused)
    if register_record:
        from repro.configs import registry
        best_traffic = defn.traffic(shapes, best_blocks, dtype_bytes)
        rec = registry.register_kernel_tune(registry.KernelTuneRecord(
            kernel=kernel, shape_key=shape_key(shapes, dtype_bytes),
            blocks=tuple(sorted(best_blocks.items())),
            modeled_seconds=best_cost.total_s,
            default_blocks=tuple(sorted(default.items())),
            default_modeled_seconds=default_cost.total_s,
            saved_bytes=best_traffic.saved_bytes,
            measured_us=measured_us, default_us=default_us, source=source,
            route=route))
        if source == "timed" and resolved != "frozen":
            db = tunedb.active_db()
            if db is not None:
                from repro.cluster.policy import current_policy
                db.record(rec, backend=jax.default_backend(),
                          mode=current_policy().mode)
    return result


def tuned_record(kernel: str, shapes: dict, *, dtype_bytes: int = 4,
                 **autotune_kwargs):
    """Registry-first tune record for (kernel, shapes, dtype).

    A hit — including a TuneDB warm-start — returns without re-racing
    (this is what makes a second benchmark run race-free); a miss runs
    `autotune` (timed under the active mode) and returns the fresh record.
    Either way the ambient KernelPolicy's tune_hits/tune_misses counter
    is bumped, same as the `tuned_call` dispatch path.
    """
    from repro.cluster.policy import current_policy
    from repro.configs import registry
    key = shape_key(shapes, dtype_bytes)
    rec = registry.get_kernel_tune(kernel, key)
    if rec is not None:
        current_policy().bump("tune_hits")
        return rec
    current_policy().bump("tune_misses")
    autotune(kernel, shapes, dtype_bytes=dtype_bytes, **autotune_kwargs)
    return registry.get_kernel_tune(kernel, key)


def tuned_blocks(kernel: str, shapes: dict, *, dtype_bytes: int = 4) -> dict:
    """Registry-cached tuned blocks for (kernel, shapes, dtype); tunes on miss."""
    return dict(tuned_record(kernel, shapes, dtype_bytes=dtype_bytes).blocks)
