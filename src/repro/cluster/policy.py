"""KernelPolicy — one object steering every kernel-dispatch decision.

MemPool programs one substrate through several runtimes; which kernel body
actually runs (hand-tuned blocking, fused producer-consumer kernel, jnp
reference, interpreter) used to be steered by two side channels: a
fused-route bool threaded through `ArchConfig` into the model files, and a
backend probe buried in `kernels/ops.py`. Both now live here:

  KernelPolicy(mode="tuned" | "fused" | "reference" | "interpret",
               overrides={op_name: mode_or_blocks})

* ``tuned``     — Pallas kernels with autotuned (registry-cached) blockings;
                  autotune-on-miss. The default.
* ``fused``     — same, plus the model stack takes the fused
                  producer-consumer route (kernels/fused.py) wherever a
                  block's norm kind allows it.
* ``reference`` — the pure-jnp oracles from kernels/ref.py.
* ``interpret`` — Pallas bodies through the interpreter. For the CPU only
                  (off-TPU backends always interpret, whatever the mode);
                  on a TPU backend asking for it raises instead of hiding
                  the device behind the interpreter.

``overrides`` refines single ops: a mode string re-routes that op only
(``{"matmul": "reference"}``), a block dict pins its blocking for
``tuned_call`` (``{"matmul": {"bm": 64, "bn": 64, "bk": 64}}``).

The active policy is an explicitly scoped stack: ``with use_policy(p): ...``
(or ``with cluster.policy(...)``). Dispatch sites read ``current_policy()``
at trace time, so a policy is baked into whatever jit trace it was active
under — exactly like the config bool it replaces, but in one place. With no
scope active, the default policy applies; ``REPRO_INTERPRET=1`` in the
environment turns the default into ``interpret`` mode (the old env path),
and ``REPRO_KERNEL_POLICY`` picks any default mode outright.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Any, Iterator, Mapping

MODES = ("tuned", "fused", "reference", "interpret")


TUNINGS = ("auto", "timed", "modeled", "frozen")


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """Kernel-selection policy: a global mode plus per-op overrides.

    ``tuning`` steers how autotune-on-miss picks a blocking: ``"timed"``
    races the top modeled candidates plus the default on device and keeps
    the measured winner (writing it through to the active TuneDB);
    ``"modeled"`` keeps the legacy score-only pick; ``"frozen"`` is the CI
    determinism mode — score-only pick, and the TuneDB is never written.
    ``"auto"`` (the default) defers to ``REPRO_TUNE_MODE`` (itself
    defaulting to ``timed``) — see ``kernels.tunedb.tune_mode``.

    ``stats`` is a mutable per-instance counter dict (ref_calls,
    pallas_calls, tune_hits, tune_misses, tune_races, block_overrides)
    filled in by the dispatch sites — excluded from equality so two
    policies with the same knobs compare equal regardless of traffic.
    """

    mode: str = "tuned"
    overrides: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    tuning: str = "auto"
    stats: dict = dataclasses.field(default_factory=dict, compare=False,
                                    repr=False)

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"unknown policy mode {self.mode!r}; "
                             f"expected one of {MODES}")
        if self.tuning not in TUNINGS:
            raise ValueError(f"unknown tuning {self.tuning!r}; "
                             f"expected one of {TUNINGS}")
        for op, v in self.overrides.items():
            if isinstance(v, str):
                if v not in MODES:
                    raise ValueError(f"override for {op!r}: unknown mode "
                                     f"{v!r}; expected one of {MODES}")
            elif not isinstance(v, Mapping):
                raise TypeError(f"override for {op!r} must be a mode string "
                                f"or a block dict, got {type(v).__name__}")
        if "interpret" in (self.mode, *self.overrides.values()):
            _refuse_interpret_on_tpu(f"KernelPolicy({self.mode!r}, "
                                     f"overrides={dict(self.overrides)!r})")

    # -- per-op resolution ----------------------------------------------------
    def mode_for(self, op: str) -> str:
        """The mode governing `op`: its string override, else the global."""
        o = self.overrides.get(op)
        return o if isinstance(o, str) else self.mode

    def blocks_for(self, op: str) -> dict | None:
        """Pinned blocking for `op` (a dict override), or None to autotune."""
        o = self.overrides.get(op)
        return dict(o) if isinstance(o, Mapping) else None

    def interpret_for(self, op: str) -> bool:
        """Should `op`'s Pallas body run interpreted? Always off-TPU
        (numerics-identical, which is what the allclose tests against ref.py
        verify); never on a TPU, where the ``interpret`` mode raises."""
        import jax
        if jax.default_backend() != "tpu":
            return True
        if self.mode_for(op) == "interpret":
            _refuse_interpret_on_tpu(f"the policy's mode for {op!r}")
        return False

    @property
    def fused(self) -> bool:
        """Does the model stack take the fused producer-consumer route?"""
        return self.mode == "fused"

    # -- dispatch (the tuned_call body) ---------------------------------------
    def call(self, name: str, *operands, **kwargs):
        """Run kernel `name` under this policy: reference short-circuit,
        pinned blocks, or autotuned (registry-cached, tune-on-miss) blocks.

        This is the single place fused/tuned/reference selection and
        autotune-on-miss live; ``ops.tuned_call`` delegates here.
        """
        from repro.configs import registry
        from repro.kernels import ops, pipeline

        desc = ops.OPS[name]
        if self.mode_for(name) == "reference":
            self.bump("ref_calls")
            return desc.reference(*operands, **kwargs)
        blocks = self.blocks_for(name)
        if blocks is None:
            shapes = desc.shapes(*operands)
            dtype_bytes = operands[desc.streamed_operand].dtype.itemsize
            key = pipeline.shape_key(shapes, dtype_bytes)
            rec = registry.get_kernel_tune(name, key)
            if rec is None:
                # miss -> autotune: under "timed" tuning this compiles and
                # races the top modeled candidates on synthetic operands
                # (the real ones may be tracers) and keeps the measured
                # winner, bumping tune_races and writing the TuneDB
                self.bump("tune_misses")
                tune = pipeline.autotune(
                    name, shapes, dtype_bytes=dtype_bytes,
                    mode=None if self.tuning == "auto" else self.tuning)
                blocks, route = dict(tune.blocks), tune.route
            else:
                self.bump("tune_hits")
                blocks, route = dict(rec.blocks), rec.route
            if route == "unfused" and desc.composition is not None:
                # the race demoted this fusion on these shapes — run the
                # unfused composition of primitive kernels instead (blocks
                # stay recorded in case the composition route is retired)
                self.bump("unfused_routes")
                return desc.composition(*operands, **kwargs)
        else:
            self.bump("block_overrides")
        return desc.wrapper(*operands, **blocks, **kwargs)

    # -- bookkeeping ----------------------------------------------------------
    def bump(self, key: str) -> None:
        self.stats[key] = self.stats.get(key, 0) + 1

    def describe(self) -> dict:
        """JSON-able snapshot: knobs + traffic counters (for bench records,
        program reports, and compile-cache fingerprints)."""
        return {
            "mode": self.mode,
            "overrides": {k: (v if isinstance(v, str) else dict(v))
                          for k, v in sorted(self.overrides.items())},
            "tuning": self.tuning,
            "stats": dict(self.stats),
        }

    def fingerprint(self) -> str:
        """Stable key component (knobs only — stats excluded)."""
        d = self.describe()
        d.pop("stats")
        return repr(sorted((k, repr(v)) for k, v in d.items()))


def _refuse_interpret_on_tpu(what: str) -> None:
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what} asks for interpreted Pallas kernels on a TPU backend; "
            f"interpret mode is for the CPU only (unset REPRO_INTERPRET / "
            f"REPRO_KERNEL_POLICY=interpret on the chip)")


# ----------------------------------------------------------------------------
# The active-policy stack
# ----------------------------------------------------------------------------

_STACK: list[KernelPolicy] = []


def _env_truthy(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() not in ("", "0", "false")


def default_policy() -> KernelPolicy:
    """The ambient policy when no scope is active. ``REPRO_KERNEL_POLICY``
    selects the mode; ``REPRO_INTERPRET=1`` (the legacy env path) maps to
    ``interpret``, which raises on a TPU backend."""
    mode = os.environ.get("REPRO_KERNEL_POLICY", "").strip()
    if not mode:
        mode = "interpret" if _env_truthy("REPRO_INTERPRET") else "tuned"
    return KernelPolicy(mode=mode)


def current_policy() -> KernelPolicy:
    return _STACK[-1] if _STACK else default_policy()


def as_policy(p: "KernelPolicy | str | None") -> KernelPolicy:
    """Coerce a policy spec: a KernelPolicy, a bare mode string, or None
    (-> the environment-derived default)."""
    if isinstance(p, KernelPolicy):
        return p
    if p is None:
        return default_policy()
    if isinstance(p, str):
        return KernelPolicy(mode=p)
    raise TypeError(f"cannot make a KernelPolicy from {type(p).__name__}")


@contextlib.contextmanager
def use_policy(p: "KernelPolicy | str | None") -> Iterator[KernelPolicy]:
    """Scope `p` as the active policy (nests; innermost wins)."""
    pol = as_policy(p)
    _STACK.append(pol)
    try:
        yield pol
    finally:
        _STACK.pop()


@contextlib.contextmanager
def scoped(p: "KernelPolicy | str | None") -> Iterator[KernelPolicy]:
    """Like use_policy, but None means *inherit the ambient policy* rather
    than reset to the default — the step-factory helper."""
    if p is None:
        yield current_policy()
    else:
        with use_policy(p) as pol:
            yield pol
