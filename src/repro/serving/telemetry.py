"""Serving layer 2 — per-stage telemetry feeding the online replanner.

The paper's Sec. V re-partitions "when profiling information deviates from
predictions". Here the serving engine measures each pipeline stage's wall
time (a jitted single-stage probe — ``PipelinedDecoder.build_stage_probe`` —
timed host-side), folds an EMA of the measurements into
``OnlineReplanner.observe()`` every ``interval`` steps, and heartbeats the
``ResourceManager`` for every stage that answered its probe.

Scale normalization: analytic predictions are in modeled device-seconds
while measurements are host wall time, so raw ratios are meaningless.
Observations are rescaled by anchoring the fastest-relative stage at its
prediction (``scale = max_i pred_i/obs_i``, so that stage reads exactly at
spec and every other stage at or above it) — a *uniformly* slow host never
triggers a re-plan (re-placing stages cannot fix global slowness), while a
relative straggler stands out by its slowdown no matter how large its
predicted share.

``inject(stage, factor)`` multiplies a stage's measured time before
normalization — the straggler-injection hook used by tests, the serve CLI
and the throughput benchmark to exercise the live re-plan path on
homogeneous hardware.

Program spans: ``span(name, **meta)`` opens a
``jax.profiler.TraceAnnotation`` (so a profiler trace shows the span on its
host plane, on the device planes' clock) and adds the span's host-clock
duration to a running total and count per name. The totals are always on
and constant in size; readers take deltas, like ``wall_s``. Python's
garbage-collection pauses are recorded the same way, as ``py.gc``, through
one process-wide ``gc.callbacks`` hook.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax

from repro.core.planner import PlacementSpec
from repro.runtime.ft import HeartbeatMonitor, OnlineReplanner

#: the spans ``ServingEngine.step`` opens, outermost first
SPANS = ("serve.step", "serve.admit", "serve.chunk", "serve.grow",
         "serve.dispatch", "serve.device_wait", "serve.sample", "serve.emit",
         "serve.telemetry")
GC_SPAN = "py.gc"


class _GcPauses:
    """Garbage-collection pauses as ``py.gc`` spans with process totals.
    Collection is process-wide, so there is one hook per process."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self._open = None
        self.installed = False

    def install(self) -> None:
        if not self.installed:
            gc.callbacks.append(self._on_gc)
            self.installed = True

    def _on_gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            ann = jax.profiler.TraceAnnotation(
                GC_SPAN, generation=info["generation"])
            ann.__enter__()
            self._open = (ann, time.perf_counter())
        elif self._open is not None:
            ann, t0 = self._open
            self._open = None
            ann.__exit__(None, None, None)
            self.seconds += time.perf_counter() - t0
            self.count += 1


GC_PAUSES = _GcPauses()


@dataclasses.dataclass
class StageTelemetry:
    replanner: OnlineReplanner
    monitor: Optional[HeartbeatMonitor] = None
    interval: int = 8                   # observe() every N engine steps
    ema: float = 0.5                    # new-sample weight
    _stage_ema: Dict[int, float] = dataclasses.field(default_factory=dict)
    _inject: Dict[int, float] = dataclasses.field(default_factory=dict)
    wall_s: float = 0.0
    steps_recorded: int = 0
    # paged decode attention, summed over decode calls: pages of live rows
    # (what the kernel streams) and pages the block tables span
    attn_live_pages: int = 0
    attn_table_pages: int = 0
    observations: int = 0
    span_s: Dict[str, float] = dataclasses.field(
        init=False, default_factory=lambda: dict.fromkeys(SPANS, 0.0))
    span_n: Dict[str, int] = dataclasses.field(
        init=False, default_factory=lambda: dict.fromkeys(SPANS, 0))

    def __post_init__(self):
        GC_PAUSES.install()
        self._gc_base = (GC_PAUSES.seconds, GC_PAUSES.count)

    # -- fault injection ----------------------------------------------------
    def inject(self, stage: int, factor: float) -> None:
        """Multiply stage ``stage``'s measured time by ``factor`` (straggler
        injection; factor 1.0 clears)."""
        if factor == 1.0:
            self._inject.pop(stage, None)
        else:
            self._inject[stage] = factor

    # -- measurement --------------------------------------------------------
    def record_step(self, wall_dt: float) -> None:
        self.wall_s += wall_dt
        self.steps_recorded += 1

    def record_decode_attention(self, live_pages: int,
                                table_pages: int) -> None:
        self.attn_live_pages += live_pages
        self.attn_table_pages += table_pages

    @contextlib.contextmanager
    def span(self, name: str, **meta) -> Iterator[None]:
        """A program span: a profiler ``TraceAnnotation`` carrying
        ``meta``, whose host-clock duration adds to ``name``'s totals. It
        adds no device work and no device sync."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, **meta):
                yield
        finally:
            self.span_s[name] = self.span_s.get(name, 0.0) \
                + time.perf_counter() - t0
            self.span_n[name] = self.span_n.get(name, 0) + 1

    def span_totals(self) -> Dict[str, Tuple[float, int]]:
        """Seconds and count of every span name since the last reset,
        ``py.gc`` included (pauses anywhere in the process)."""
        out = {n: (self.span_s[n], self.span_n[n]) for n in self.span_s}
        out[GC_SPAN] = (GC_PAUSES.seconds - self._gc_base[0],
                        GC_PAUSES.count - self._gc_base[1])
        return out

    def reset_measurements(self) -> None:
        """Zero the wall-time and span accounting (warmup reset / benchmark
        phase boundaries) without touching replanner state or stage
        EMAs."""
        self.wall_s = 0.0
        self.steps_recorded = 0
        self.attn_live_pages = 0
        self.attn_table_pages = 0
        self.span_s = dict.fromkeys(SPANS, 0.0)
        self.span_n = dict.fromkeys(SPANS, 0)
        self._gc_base = (GC_PAUSES.seconds, GC_PAUSES.count)

    def record_stage_times(self, times: Sequence[float]) -> None:
        """Fold one per-stage probe (host wall seconds, stage order) into the
        EMA. Heartbeats every device hosting a stage that answered."""
        current = self.replanner.current
        for i, t in enumerate(times):
            t = t * self._inject.get(i, 1.0)
            prev = self._stage_ema.get(i)
            self._stage_ema[i] = t if prev is None else \
                (1 - self.ema) * prev + self.ema * t
            if current is not None and i < len(current.placement.stages):
                self.replanner.rm.heartbeat(current.placement.stages[i].device)

    def predicted_shares(self) -> List[float]:
        """Per-stage predicted time fractions (LocalDecodeBackend fallback:
        attribute a whole-step measurement proportionally, so only *injected*
        deviation registers)."""
        cur = self.replanner.current
        if cur is None:
            return []
        total = sum(cur.stage_times) or 1.0
        return [t / total for t in cur.stage_times]

    # -- the observe tick ---------------------------------------------------
    def scaled_observations(self) -> Dict[tuple, float]:
        """EMA measurements keyed (device, stage_idx), rescaled into the
        prediction's units by anchoring on the *fastest-relative* stage:
        ``scale = max_i pred_i / obs_i``, so the best-behaved stage reads
        exactly at spec and a straggler stands out by its relative slowdown —
        even when it dominates the predicted total (a total-sum rescale
        would absorb it)."""
        cur = self.replanner.current
        if cur is None or not self._stage_ema:
            return {}
        stages = cur.placement.stages
        obs = {i: t for i, t in self._stage_ema.items()
               if i < len(stages) and t > 0.0}
        if not obs:
            return {}
        scale = max(cur.stage_times[i] / t for i, t in obs.items())
        if scale <= 0.0:
            return {}
        return {(stages[i].device, i): t * scale for i, t in obs.items()}

    def maybe_observe(self, step: int) -> Optional[PlacementSpec]:
        """Every ``interval`` steps: sweep heartbeats and feed the scaled
        observations to the replanner. Returns the new PlacementSpec when
        the replanner decided to re-plan (the engine then swaps boundaries)."""
        if step == 0 or step % self.interval:
            return None
        if self.monitor is not None:
            self.monitor.sweep()
        scaled = self.scaled_observations()
        self.observations += 1
        new_spec = self.replanner.observe(scaled)
        if new_spec is not None:
            # measurements were relative to the old placement
            self._stage_ema.clear()
        return new_spec
