"""repro.cluster — the Cluster/Session façade and the kernel policy.

`KernelPolicy` (policy.py) is imported eagerly: it is dependency-light and
the kernel layer (kernels/ops.py) and model stack read it at dispatch time.
The Cluster + program classes (session.py) pull in the whole model/runtime
stack, so they load lazily on first attribute access — `import
repro.cluster` from a kernel module stays cheap and cycle-free.
"""

from repro.cluster.policy import (KernelPolicy, as_policy,  # noqa: F401
                                  current_policy, default_policy, scoped,
                                  use_policy)
from repro.kernels.tunedb import TuneDB  # noqa: F401  (dependency-light)

_SESSION_EXPORTS = ("Cluster", "Program", "TrainProgram",
                    "ServeSessionProgram", "ShardedServeSessionProgram",
                    "DryRunProgram", "BenchProgram",
                    "CompiledTrain", "CompiledServeSession",
                    "CompiledShardedServeSession",
                    "CompiledDryRun", "CompiledBench")

__all__ = list(_SESSION_EXPORTS) + [
    "KernelPolicy", "TuneDB", "as_policy", "current_policy",
    "default_policy", "scoped", "use_policy",
]


def __getattr__(name):
    if name in _SESSION_EXPORTS:
        from repro.cluster import session
        return getattr(session, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
