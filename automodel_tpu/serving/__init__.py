"""Continuous-batching serving engine over a paged KV cache.

- kv_pages.py:     global refcounted page pool + per-request page tables
                   (GQA + MLA layouts, copy-on-write sharing; mesh-sharded
                   under tp — pages global, per-page head dim partitioned)
- prefix_cache.py: radix tree over known tokens at page granularity —
                   cross-request prefix sharing + LRU reclaim
- scheduler.py:    admission / chunked-prefill / preemption scheduling
- engine.py:       the jitted fixed-shape step (single-chip or TP/EP-
                   sharded over a mesh slice) + serve_batch() host loop
- router.py:       data-parallel engine replicas + per-replica admission
                   (sticky prefix affinity, least-loaded-by-free-pages),
                   plus disaggregated prefill/decode replica classes and
                   the elastic prefill autoscaler
- kv_transfer.py:  page-granular KV movement between engine pools — the
                   device half of the prefill→decode handoff
- frontend.py:     online asyncio serve loop — live admission, per-request
                   token streams with backpressure, deadline load shedding
- plan_wire.py:    StepPlan wire format + multi-host plan broadcast
                   (lead process stays single-brained, followers replay;
                   bounded-timeout follower acks surface a dead follower
                   as a named ReplicaFailure instead of a silent hang)
- resilience.py:   serving-tier failure handling — per-replica health
                   state machine, evacuate-and-requeue recovery, disagg
                   degraded-mode routing, transfer retry with backoff
- ops/paged_attention.py holds the ragged paged-attention op it runs on.
"""

from automodel_tpu.serving.engine import (
    Request,
    ServingConfig,
    ServingEngine,
    split_layer_stacks,
)
from automodel_tpu.serving.frontend import (
    DisaggOnlineFrontend,
    FrontendConfig,
    OnlineFrontend,
    TokenStream,
)
from automodel_tpu.serving.kv_pages import PageAllocator, pages_for
from automodel_tpu.serving.kv_transfer import KVTransfer
from automodel_tpu.serving.plan_wire import (
    PlanFollower,
    make_plan_broadcast,
    pack_plan,
    pack_stop,
    unpack_plan,
)
from automodel_tpu.serving.router import (
    AutoscaleConfig,
    DisaggConfig,
    DisaggRouter,
    OnlineRouter,
    QueueAutoscaler,
    ReplicaRouter,
    ServeMeshConfig,
)
from automodel_tpu.serving.prefix_cache import (
    PrefixCache,
    PrefixCacheConfig,
    PrefixMatch,
)
from automodel_tpu.serving.resilience import (
    HealthBoard,
    ReplicaFailure,
    ReplicaHealth,
    ServeResilienceConfig,
    pool_identity_ok,
)
from automodel_tpu.serving.scheduler import Scheduler, StepPlan
from automodel_tpu.speculative.serve_draft import (
    DFlashDraftSource,
    DraftSource,
    EagleDraftSource,
    NgramDraftSource,
    SpeculativeConfig,
)

__all__ = [
    "AutoscaleConfig",
    "DFlashDraftSource",
    "DisaggConfig",
    "DisaggOnlineFrontend",
    "DisaggRouter",
    "DraftSource",
    "EagleDraftSource",
    "FrontendConfig",
    "HealthBoard",
    "KVTransfer",
    "NgramDraftSource",
    "OnlineFrontend",
    "OnlineRouter",
    "PageAllocator",
    "PlanFollower",
    "PrefixCache",
    "PrefixCacheConfig",
    "PrefixMatch",
    "QueueAutoscaler",
    "ReplicaFailure",
    "ReplicaHealth",
    "ReplicaRouter",
    "Request",
    "Scheduler",
    "ServeMeshConfig",
    "ServeResilienceConfig",
    "ServingConfig",
    "ServingEngine",
    "SpeculativeConfig",
    "StepPlan",
    "TokenStream",
    "make_plan_broadcast",
    "pack_plan",
    "pack_stop",
    "pool_identity_ok",
    "split_layer_stacks",
    "unpack_plan",
]
