from .engine import (StallClock, init_session_state,  # noqa: F401
                     make_nan_scan, make_session_chunk, make_session_refill,
                     make_slot_corrupt, make_slot_restore,
                     make_slot_snapshot, make_train_chunk)
from .faults import (Fault, FaultPlan, InjectedFault,  # noqa: F401
                     SessionCrashed, SessionWedged)
from .groups import (GroupPlan, GroupRuntime, GroupView,  # noqa: F401
                     MeshScheduler, ShardedServeSession)
from .journal import (Journal, ReplayedRequest, ReplaySummary,  # noqa: F401
                      read_events, replay)
from .kvpool import PagedKV, PagePool, PrefixCache, page_digests  # noqa: F401
from .scheduler import (QueueFull, Request, RequestFailed,  # noqa: F401
                        RequestHandle, SlotScheduler, deserialize_request,
                        serialize_request)
from .train_loop import TrainLoop, TrainLoopConfig  # noqa: F401
from .serve_loop import ServeSession  # noqa: F401
from .compile_cache import CompileCache  # noqa: F401
