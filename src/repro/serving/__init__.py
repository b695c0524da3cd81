"""Serving engine subsystem (DESIGN.md §Serving engine, §Paged KV cache,
§AOT warmup & chunked prefill).

Four decoupled layers over the planner/pipeline/ft stack:

1. **scheduler** — continuous-batching slot scheduler (FIFO admission,
   per-request EOS/length completion, immediate slot recycling, a PREFILL
   state for slots whose prompt is still streaming in) and the
   ``PagePool`` free-list allocator for the paged KV layout;
2. **telemetry** — per-stage wall-time probes folded into
   ``OnlineReplanner.observe()`` with scale normalization and straggler
   injection, plus ResourceManager heartbeats;
3. **aot** — the AOT compilation ledger: ``CompileMonitor`` counts true
   XLA compilations at the runtime level, ``AotRegistry``/``AotFn`` manage
   every jitted serving function so ``ServingEngine.warmup()`` can compile
   the full shape inventory up front and steady-state serving performs
   ZERO new compilations (post-freeze compiles/stalls surface in
   ``stats()``);
4. **engine** — ``ServingEngine``: paged per-slot KV decode (block-table-
   indexed shared page pools, one-call batched prefill OR chunked prefill
   interleaved with decode ticks for long prompts, page recycling —
   unbounded engine lifetime) with the legacy shared-position-timeline
   layout kept for recurrent-state/SWA models, over pluggable backends
   (shard_map pipelined / local single-process) with live stage-boundary
   swaps that migrate the KV state in place. Plans are ``PlacementSpec``
   segment placements (possibly non-prefix); decoding is greedy or
   temperature/top-k sampled (**sampling** — per-request PRNG threading
   keeps sampled streams batch-independent).

Plus **disagg** (DESIGN.md §Disaggregated prefill/decode): a prefill-role
engine seals each prompt's KV pages into a ``TransferManifest`` that a
decode-role engine unseals into its own pool, with ``DisaggOrchestrator``
routing, back-pressure, and bit-identical streams, and
``plan_disagg_roles`` picking role placement across trust domains.

And **faults** (DESIGN.md §Fault injection & recovery): ``FaultPlane``, a
deterministic seeded chaos-injection plane with sites at every
trust/failure boundary (device death, stage stalls, sealed-payload
tampering, handoff drop/delay, pool-exhaustion storms), paired with the
engine's recovery ladder (``stats()["recovery"]``) — every injected fault
is absorbed bit-identically or surfaced explicitly, never silent.
"""
from .aot import MONITOR, AotFn, AotRegistry, CompileMonitor, CompileStall
from .disagg import (DisaggOrchestrator, PrefillEngine, RoleCandidate,
                     RolePlan, build_disagg, plan_disagg_roles)
from .faults import FaultConfig, FaultPlane
from .engine import (EngineConfig, EngineEvent, LocalDecodeBackend,
                     PagedLocalBackend, PagedPipelinedBackend,
                     PipelinedDecodeBackend, ServingEngine)
from .sampling import TokenSampler
from .scheduler import (HANDOFF, PagePool, Request, SlotScheduler,
                        TransferManifest)
from .telemetry import StageTelemetry

__all__ = [
    "AotFn", "AotRegistry", "CompileMonitor", "CompileStall",
    "DisaggOrchestrator", "EngineConfig", "EngineEvent", "FaultConfig",
    "FaultPlane", "HANDOFF",
    "LocalDecodeBackend", "MONITOR", "PagePool", "PagedLocalBackend",
    "PagedPipelinedBackend", "PipelinedDecodeBackend", "PrefillEngine",
    "Request", "RoleCandidate", "RolePlan", "ServingEngine", "SlotScheduler",
    "StageTelemetry", "TokenSampler", "TransferManifest", "build_disagg",
    "plan_disagg_roles",
]
