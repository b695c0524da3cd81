"""Serving layer 3 — the continuous-batching engine with live re-planning.

``ServingEngine`` turns the one-shot batch-decode demo into a long-lived
request server (the paper's Fig. 2 loop as a service):

* **paged per-slot KV cache (default)** — KV lives in shared page pools
  indexed by per-slot block tables (``kv_layout="paged"``, DESIGN.md §Paged
  KV cache / §Demand paging & copy-on-write). Under the default
  ``page_policy="demand"``, admission takes only the *prompt's* pages —
  identical prompt-prefix pages are adopted from a copy-on-write index —
  block tables grow one ref-counted page per decode step (forking shared
  pages before the first divergent write), and pool exhaustion preempts
  the youngest slot (its generated tokens requeue as a prompt extension)
  instead of gating admission. ``page_policy="reserve"`` keeps the PR 5
  worst-case reservation as the property-test oracle. Either way the whole
  prompt prefills in ONE jitted call (``prefill_at_fn``, right-padded to
  power-of-two buckets), completion recycles pages, and the engine runs
  indefinitely: no shared-timeline horizon, per-step attention bounded by
  per-request capacity, not lifetime. Positions are 0-based per request,
  which *removes* the ``start``-mask and RoPE-offset machinery rather
  than hiding it.
* **legacy shared position timeline** (``kv_layout="timeline"``, and the
  automatic fallback for recurrent-state / SWA / quantized-cache models) —
  one dense cache advancing a global position per step; offset prefill one
  token at a time with per-slot ``start`` masks. The horizon is now a
  back-pressure bound, not a crash: admission only accepts requests whose
  worst-case generation ends inside ``max_seq``, and the engine reports
  ``stalled`` when the head of the queue can never fit.
* **pluggable decode backends** — ``PagedPipelinedBackend`` /
  ``PipelinedDecodeBackend`` run the shard_map pipelined decoder over the
  ``pod`` axis (stage boundaries from the placement solver, sealed
  boundaries); ``PagedLocalBackend`` / ``LocalDecodeBackend`` are the
  single-device backends, used when there is no mesh or
  ``num_stages == 1``.
* **telemetry → live re-plan swap** — every ``telemetry.interval`` steps the
  engine probes per-stage wall time, feeds ``OnlineReplanner.observe()``,
  and on a re-plan builds a decoder for the new boundaries and migrates the
  staged KV state in place via ``PipelinedDecoder.restage_cache`` (dense
  caches and page pools stage/restage identically along the layer dim) —
  decode continues token-exactly across the swap.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import math
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.planner import (InfeasibleError, PlacementSpec,
                                profiles_from_arch)
from repro.core.privacy import LM_SIM_DELTA
from repro.enclave import sealing
from repro.enclave.domain import ResourceManager, two_enclave_manager
from repro.runtime.ft import HeartbeatMonitor, OnlineReplanner
from repro.runtime.pipeline import PipelinedDecoder, pipeline_applicable
from repro.serving.aot import MONITOR, AotRegistry
from repro.serving.faults import FaultPlane
from repro.serving.sampling import TokenSampler
from repro.serving.scheduler import (QUEUED, RUNNING, PagePool, Request,
                                     SlotScheduler, TransferManifest)
from repro.serving.telemetry import StageTelemetry


@dataclasses.dataclass
class EngineConfig:
    num_slots: int = 4                  # decode batch == KV slots
    num_stages: int = 2
    num_microbatches: int = 2
    max_seq: int = 256                  # shared-timeline horizon (legacy)
    prompt_capacity: int = 32           # max admissible prompt length
    # paged KV cache (default layout; "timeline" = legacy shared horizon)
    kv_layout: str = "paged"
    page_size: int = 16                 # tokens per KV page
    request_capacity: int = 0           # max prompt+max_new (0 = auto)
    num_pages: int = 0                  # shared pool size (0 = auto: all
    #                                     slots at full request_capacity)
    # page allocation policy (DESIGN.md §Demand paging):
    #   "demand"  — block tables grow one page per decode step; admission
    #               needs only the prompt's pages (+1); identical prompt-
    #               prefix pages are shared copy-on-write; pool exhaustion
    #               preempts the lowest-priority slot instead of gating
    #   "reserve" — the PR 5 baseline: worst-case page count reserved at
    #               admission (kept as the property-test oracle)
    page_policy: str = "demand"
    # preemption policy (DESIGN.md §Two-tier KV & swap; demand paging only):
    #   "auto"      — resolve by layout at construction: "swap" on the paged
    #                 layout, "recompute" on the timeline fallback (sliding-
    #                 window / quantized caches have no page pool to gather
    #                 from). stats()["preempt_policy"] reports the resolved
    #                 value.
    #   "swap"      — seal the victim's private pages through the lossless
    #                 bit-cipher into host swap space and restore them at
    #                 re-admission: resume is O(pages transferred). COW-
    #                 shared pages are never spilled — the swap manifest
    #                 pins them in the prefix index and re-adopts in place.
    #                 Raises ValueError on timeline-layout models.
    #   "recompute" — the PR 6 baseline (kept as the oracle): discard KV,
    #                 re-prefill prompt+generated teacher-forced, O(tokens).
    # Both produce bit-identical streams (asserted by tests/test_swap.py).
    preempt_policy: str = "auto"
    # disaggregated prefill/decode (DESIGN.md §Disaggregated prefill/decode;
    # serving/disagg.py): "" = monolithic, "prefill" = this engine seals and
    # exports finished prefills (export_transfer), "decode" = it ingests
    # TransferManifests from a prefill peer (ingest_transfer). Either role
    # requires the paged layout + demand paging; timeline-layout models
    # raise ValueError at construction.
    disagg_role: str = ""
    prefix_sharing: bool = True         # COW prefix index (demand only)
    decode_cow: bool = True             # register pages COMPLETED during
    #                                     decode in the COW index too, so
    #                                     identical continuations (fan-out
    #                                     resubmissions) share KV
    batched_prefill: bool = True        # whole-prompt prefill in one call
    prefill_pack: int = 0               # pack up to this many short prompts
    #                                     into ONE shared bucketed prefill
    #                                     call with per-request logit
    #                                     extraction (0/1 = off; paged +
    #                                     batched_prefill only) — amortizes
    #                                     dispatch on the prefill role,
    #                                     streams unchanged
    seal_boundary: bool = True
    use_kernel: bool = False
    solver: str = "dp"
    space: str = "segment"              # PlacementSpec search space
    plan_n: int = 10_000
    delta: float = LM_SIM_DELTA
    telemetry_interval: int = 8
    deviation_threshold: float = 1.5
    heartbeat_timeout_s: float = 10.0
    allow_swap: bool = True
    # sampling (ROADMAP (g)): 0.0 = greedy argmax (deterministic)
    temperature: float = 0.0
    top_k: int = 0
    sample_seed: int = 0
    # AOT warmup + chunked prefill (DESIGN.md §AOT warmup & chunked prefill)
    warmup: bool = False                # compile every serving shape at
    #                                     startup; steady state then performs
    #                                     ZERO new XLA compilations (asserted
    #                                     via stats()["post_warmup_compiles"])
    warmup_layouts: int = 8             # swap-target stage layouts to prewarm
    prefill_chunk: int = 0              # long prompts prefill in chunks of
    #                                     this many tokens, at most one chunk
    #                                     per engine step between decode
    #                                     ticks (0 = whole-prompt admission)
    # host-history ring-buffer caps: events / finished transcripts /
    # admission latencies keep only this many entries (lifetime aggregates
    # in stats() stay exact), so a week-long serve holds constant host
    # memory
    events_cap: int = 4096
    finished_cap: int = 4096
    admission_cap: int = 4096
    # chaos-injection fault plane (serving/faults.py): a FaultConfig, or
    # None to serve fault-free. Injection counters surface in
    # stats()["faults"], the recovery ladder in stats()["recovery"] —
    # every injected fault is either absorbed by a named recovery rung or
    # surfaced as an explicit per-request failure, never a silent drop.
    faults: Any = None


# ---------------------------------------------------------------------------
# Decode backends
# ---------------------------------------------------------------------------
class LocalDecodeBackend:
    """Single-process backend: jitted ``decode_fn`` over one dense cache.

    Stage boundaries are tracked as metadata (the planner/telemetry loop
    still runs) but computation is not staged, so ``swap`` moves no state —
    it reports ``migrated=False`` and the engine records the event."""

    migrates_cache = False

    def __init__(self, api, params, cfg: EngineConfig,
                 stage_blocks: Sequence[int],
                 aot: Optional[AotRegistry] = None):
        self.api, self.params, self.cfg = api, params, cfg
        self.seg = api.model.segments[0]
        self.stage_blocks = tuple(stage_blocks)
        self.aot = aot or AotRegistry()
        self.reset_state()
        # single-device backend: AOT dispatch through stored Compiled
        # executables is the zero-recompile path (serving/aot.py). The slot
        # index is traced (not a static eager index) so one compiled insert
        # covers every slot.
        self._step = self.aot.wrap("decode_step", api.decode_fn)

        def insert(body, start, upd_body, upd_start, b):
            body = jax.tree.map(
                lambda g, s: jax.lax.dynamic_update_slice_in_dim(
                    g, s, b, axis=1), body, upd_body)
            return body, jax.lax.dynamic_update_slice(start, upd_start, (b,))

        self._insert = self.aot.wrap("insert", insert)

    def reset_state(self) -> None:
        cfg = self.cfg
        cache = self.api.init_cache(cfg.num_slots, cfg.max_seq)
        cache["len"] = jnp.int32(cfg.prompt_capacity)
        cache["start"] = jnp.full((cfg.num_slots,), cfg.prompt_capacity,
                                  jnp.int32)
        self.cache = cache

    @property
    def cache_len(self) -> int:
        return int(self.cache["len"])

    def step(self, tokens: jnp.ndarray, key) -> jnp.ndarray:
        logits, self.cache = self._step(self.params, self.cache,
                                        {"tokens": tokens})
        return logits

    def insert_slot(self, slot: int, private_cache: Dict[str, Any]) -> None:
        name = self.seg.name
        self.cache[name], self.cache["start"] = self._insert(
            self.cache[name], self.cache["start"], private_cache[name],
            private_cache["start"], jnp.int32(slot))

    def swap(self, stage_blocks: Sequence[int]) -> bool:
        self.stage_blocks = tuple(stage_blocks)
        return True

    def stage_times(self) -> Optional[List[float]]:
        return None                     # engine falls back to attribution


class PipelinedDecodeBackend:
    """The shard_map pipelined decoder (stage s on pod s, sealed boundaries)
    with prestaged params/cache, per-slot start masks, a per-stage timing
    probe, and in-place stage-layout cache migration on swap."""

    migrates_cache = True

    def __init__(self, api, mesh, params, cfg: EngineConfig,
                 stage_blocks: Sequence[int],
                 aot: Optional[AotRegistry] = None):
        self.api, self.mesh, self.params, self.cfg = api, mesh, params, cfg
        self.seg = api.model.segments[0]
        self.aot = aot or AotRegistry()
        # decoders/step fns cached per stage layout: swapping BACK to a
        # layout reuses the same jit objects, so a previously-warmed layout
        # never recompiles (bounded by the composition count in practice;
        # warmup prewarms at most cfg.warmup_layouts of them)
        self._layouts: Dict[Tuple[int, ...], Tuple] = {}
        self._restage: Dict[Tuple, Any] = {}    # (old, new) layout pair ->
        #                                         memoized jitted restage
        self._build(stage_blocks)
        self.reset_state()
        self._insert = self.aot.wrap("insert", self._insert_impl,
                                     dispatch="jit")

    def _restage_state(self, old_dec, old_key) -> None:
        """Migrate ``self.state`` from ``old_dec``'s layout to the current
        one through a per-(old, new)-pair memoized jitted gather. The first
        occurrence of a pair AOT-warms it — a one-off wall-time cost that
        stays off the post-freeze stall ledger — and every later swap
        across the same pair dispatches through the seeded jit cache,
        stall-free."""
        pair = (old_key, self.stage_blocks)
        fn = self._restage.get(pair)
        if fn is None:
            new_dec = self.dec
            fn = self.aot.wrap(
                f"restage{pair[0]}->{pair[1]}",
                lambda st: old_dec.restage_cache(st, new_dec),
                dispatch="jit")
            self._restage[pair] = fn
            self.state = fn.warm(self.state)
        else:
            self.state = fn(self.state)

    @staticmethod
    def _insert_impl(staged, start, upd, upd_start, b):
        staged = jax.tree.map(
            lambda g, s: jax.lax.dynamic_update_slice_in_dim(g, s, b, axis=2),
            staged, upd)
        return staged, jax.lax.dynamic_update_slice(start, upd_start, (b,))

    def _build(self, stage_blocks: Sequence[int]) -> None:
        cfg = self.cfg
        self.stage_blocks = key = tuple(stage_blocks)
        hit = self._layouts.get(key)
        if hit is None:
            dec = PipelinedDecoder(
                self.api, self.mesh, num_stages=cfg.num_stages,
                num_microbatches=cfg.num_microbatches,
                seal_boundary=cfg.seal_boundary, use_kernel=cfg.use_kernel,
                stage_blocks=key)
            staged_params = dec.place_params(self.params)
            # shard_map state changes sharding between the first and
            # steady-state call -> "jit" dispatch (serving/aot.py)
            step_fn = self.aot.wrap(f"step{key}", dec.build(
                prestaged_params=True, prestaged_cache=True,
                per_slot_start=True), dispatch="jit")
            probe = self.aot.wrap(f"probe{key}", dec.build_stage_probe(),
                                  dispatch="jit")
            hit = self._layouts[key] = (dec, staged_params, step_fn, probe)
        self.dec, self.staged_params, self.step_fn, self._probe = hit
        self._probe_warm = False

    def reset_state(self) -> None:
        cfg = self.cfg
        cache = self.api.init_cache(cfg.num_slots, cfg.max_seq)
        cache["len"] = jnp.int32(cfg.prompt_capacity)
        staged, cache_len = self.dec.stage_cache(cache)
        start = jnp.full((cfg.num_slots,), cfg.prompt_capacity, jnp.int32)
        self.state = (staged, *self.dec.place_replicated((cache_len, start)))

    @property
    def cache_len(self) -> int:
        return int(self.state[1])

    def step(self, tokens: jnp.ndarray, key) -> jnp.ndarray:
        logits, self.state = self.step_fn(self.staged_params, self.state,
                                          {"tokens": tokens}, key)
        return logits

    def insert_slot(self, slot: int, private_cache: Dict[str, Any]) -> None:
        slot_staged = self.dec._stage_tree(private_cache[self.seg.name])
        staged, cache_len, start = self.state
        staged, start = self._insert(staged, start, slot_staged,
                                     private_cache["start"], jnp.int32(slot))
        self.state = (staged, cache_len, start)

    def swap(self, stage_blocks: Sequence[int]) -> bool:
        """Rebuild the decoder on the new boundaries and migrate the staged
        cache (unstage→restage composed into one gather, memoized per
        layout pair). In-flight requests keep their KV state; the next
        step() compiles the new layout."""
        old_dec, old_key = self.dec, self.stage_blocks
        self._build(stage_blocks)
        self._restage_state(old_dec, old_key)
        return True

    def stage_times(self, repeats: int = 1) -> List[float]:
        """Host-timed per-stage block scans (one microbatch of dummy work).
        First call after (re)build warms the probe compile."""
        from repro.models import layers as L
        cfg = self.cfg
        staged, cache_len, _ = self.state
        b_mb = cfg.num_slots // cfg.num_microbatches
        x = np.zeros((b_mb, 1, self.api.cfg.d_model), L.DEFAULT_DTYPE)
        per_stage = []
        for s in range(cfg.num_stages):
            (blk_p, blk_c), dev = self.dec.stage_shard(
                (self.staged_params[self.seg.name], staged), s)
            args = (blk_p, blk_c, *jax.device_put(
                (self.dec._mask[s], x, np.asarray(cache_len)), dev))
            if not self._probe_warm:
                jax.block_until_ready(self._probe(*args))
            t0 = time.perf_counter()
            for _ in range(repeats):
                jax.block_until_ready(self._probe(*args))
            dt = (time.perf_counter() - t0) / repeats
            # uneven stages are padded to bps blocks, so every probe does
            # bps blocks of work while the planner predicts counts[s]; scale
            # to per-real-block terms or small stages read as stragglers
            # (spurious derate/replan cycles after any uneven swap)
            dt *= self.dec.stage_counts[s] / self.dec.bps
            per_stage.append(dt)
        self._probe_warm = True
        return per_stage


# ---------------------------------------------------------------------------
# Paged decode backends (block-table-indexed shared page pools)
# ---------------------------------------------------------------------------
class PagedLocalBackend:
    """Single-process paged backend: jitted ``decode_paged_fn`` over shared
    page pools + per-slot block tables / seq_lens. Positions are 0-based per
    request, so there is no ``start`` mask and no timeline horizon — the
    engine runs for as long as the page pool keeps turning over."""

    migrates_cache = False

    def __init__(self, api, params, cfg: EngineConfig,
                 stage_blocks: Sequence[int], num_pages: int,
                 pages_per_slot: int, aot: Optional[AotRegistry] = None):
        self.api, self.params = api, params
        self.seg = api.model.segments[0]
        self.stage_blocks = tuple(stage_blocks)
        self.aot = aot or AotRegistry()
        self._shape = (cfg.num_slots, num_pages, cfg.page_size,
                       pages_per_slot)
        self.reset_state()
        # bound statically at jit time: the length-bounded Pallas kernel on
        # a TPU (or interpreted, with use_kernel), the jnp page gather
        # otherwise
        self.attn_path = "pallas" if cfg.use_kernel \
            or jax.default_backend() == "tpu" else "gather"
        self._step = self.aot.wrap("decode_step", functools.partial(
            api.decode_paged_fn, use_kernel=self.attn_path == "pallas"))
        seg_name = self.seg.name

        def insert(cache, kk, vv, pages, offs, slot, bt_row, seq_len):
            # kk, vv: [L, KVH, S_pad, D] -> scatter layout [S_pad, L, KVH, D]
            # pages may carry the out-of-range sentinel (num_pages) for
            # right-padding and COW-adopted shared pages: mode="drop"
            # discards those writes, so shared pages and the null page are
            # never touched by admission
            k_pool, v_pool = cache[seg_name]
            k_pool = k_pool.at[:, pages, :, offs].set(
                kk.transpose(2, 0, 1, 3), mode="drop")
            v_pool = v_pool.at[:, pages, :, offs].set(
                vv.transpose(2, 0, 1, 3), mode="drop")
            out = dict(cache)
            out[seg_name] = (k_pool, v_pool)
            out["block_tables"] = cache["block_tables"].at[slot].set(bt_row)
            out["seq_lens"] = cache["seq_lens"].at[slot].set(seq_len)
            return out

        def clear(cache, slot):
            out = dict(cache)
            out["block_tables"] = cache["block_tables"].at[slot].set(0)
            out["seq_lens"] = cache["seq_lens"].at[slot].set(0)
            return out

        def set_bt(cache, slot, idx, page):
            out = dict(cache)
            out["block_tables"] = \
                cache["block_tables"].at[slot, idx].set(page)
            return out

        def copy_pg(cache, dst, src):
            # fork: clone one physical page across every layer [L, N, ...]
            k_pool, v_pool = cache[seg_name]
            out = dict(cache)
            out[seg_name] = (k_pool.at[:, dst].set(k_pool[:, src]),
                             v_pool.at[:, dst].set(v_pool[:, src]))
            return out

        def chunk(params, cache, batch):
            # one prefill chunk against the live pools; block tables and
            # seq_lens ride along untouched (commit_slot flips the slot
            # from idle to decoding only after the LAST chunk lands)
            logits, new_pools = api.prefill_chunk_fn(
                params, {seg_name: cache[seg_name]}, batch)
            out = dict(cache)
            out.update(new_pools)
            return logits, out

        def commit(cache, slot, bt_row, seq_len):
            out = dict(cache)
            out["block_tables"] = cache["block_tables"].at[slot].set(bt_row)
            out["seq_lens"] = cache["seq_lens"].at[slot].set(seq_len)
            return out

        use_kernel = cfg.use_kernel

        def gather(cache, pages, key, ctr):
            # two-tier swap-out: gather the slot's PRIVATE pages from the
            # pools and seal them losslessly (bitcast+XOR) in one jitted
            # pass — rows whose logical page is COW-shared (or padding)
            # carry page id 0, so they gather the all-zero null page and
            # their payload rows are never restored. [MP] -> [MP, L*KVH*Pg*D]
            k_pool, v_pool = cache[seg_name]

            def sealed(pool, part):
                g = pool[:, pages].transpose(1, 0, 2, 3, 4)
                g = g.reshape(pages.shape[0], -1)
                return sealing.seal_pages(g, key, ctr, part=part,
                                          use_kernel=use_kernel)

            return sealed(k_pool, 0), sealed(v_pool, 1)

        def scatter(cache, ck, cv, pages, key, ctr):
            # swap-in: unseal the host payload and scatter each row into a
            # freshly allocated device page; rows to skip (shared pages
            # re-adopted in place, padding) carry the out-of-range sentinel
            # and are dropped — the same drop discipline as admission
            k_pool, v_pool = cache[seg_name]

            def restored(pool, c, part):
                rows = sealing.unseal_pages(c, key, ctr, pool.dtype,
                                            part=part, use_kernel=use_kernel)
                g = rows.reshape(pages.shape[0], pool.shape[0],
                                 pool.shape[2], pool.shape[3], pool.shape[4])
                return pool.at[:, pages].set(
                    g.transpose(1, 0, 2, 3, 4), mode="drop")

            out = dict(cache)
            out[seg_name] = (restored(k_pool, ck, 0), restored(v_pool, cv, 1))
            return out

        self._insert = self.aot.wrap("insert", insert)
        self._clear = self.aot.wrap("clear_slot", clear)
        self._set_bt = self.aot.wrap("set_table_entry", set_bt)
        self._copy_pg = self.aot.wrap("copy_page", copy_pg)
        self._chunk = self.aot.wrap("prefill_chunk", chunk)
        self._commit = self.aot.wrap("commit_slot", commit)
        self._gather = self.aot.wrap("gather_pages", gather)
        self._scatter = self.aot.wrap("scatter_pages", scatter)

    def reset_state(self) -> None:
        self.cache = self.api.init_paged_cache(*self._shape)

    def step(self, tokens: jnp.ndarray, key) -> jnp.ndarray:
        logits, self.cache = self._step(self.params, self.cache,
                                        {"tokens": tokens})
        return logits

    def prefill_chunk(self, toks, offset: int, chunk_len: int, bt_row,
                      pages, offs) -> jnp.ndarray:
        batch = {"tokens": toks, "offset": jnp.int32(offset),
                 "chunk_len": jnp.int32(chunk_len), "bt_row": bt_row,
                 "pages": pages, "offs": offs}
        logits, self.cache = self._chunk(self.params, self.cache, batch)
        return logits

    def commit_slot(self, slot: int, bt_row, seq_len: int) -> None:
        self.cache = self._commit(self.cache, jnp.int32(slot), bt_row,
                                  jnp.int32(seq_len))

    def insert_slot(self, slot: int, kv, pages, offs, bt_row,
                    seq_len: int) -> None:
        kk, vv = kv
        self.cache = self._insert(self.cache, kk, vv, pages, offs,
                                  jnp.int32(slot), bt_row, jnp.int32(seq_len))

    def clear_slot(self, slot: int) -> None:
        self.cache = self._clear(self.cache, jnp.int32(slot))

    def set_table_entry(self, slot: int, idx: int, page: int) -> None:
        self.cache = self._set_bt(self.cache, jnp.int32(slot),
                                  jnp.int32(idx), jnp.int32(page))

    def copy_page(self, dst: int, src: int) -> None:
        self.cache = self._copy_pg(self.cache, jnp.int32(dst),
                                   jnp.int32(src))

    def gather_pages(self, pages, key, ctr):
        """Seal ``pages`` (fixed [pages_per_slot] int32 vector; 0 = skip
        row) out of the pools. Returns (k_cipher, v_cipher) device arrays —
        the caller fetches them to host (the pinned swap tier)."""
        return self._gather(self.cache, pages, key, ctr)

    def scatter_pages(self, ck, cv, pages, key, ctr) -> None:
        """Unseal and scatter payload rows into ``pages`` (sentinel
        ``num_pages`` = drop the row)."""
        self.cache = self._scatter(self.cache, ck, cv, pages, key, ctr)

    def swap(self, stage_blocks: Sequence[int]) -> bool:
        self.stage_blocks = tuple(stage_blocks)
        return True

    def stage_times(self) -> Optional[List[float]]:
        return None                     # engine falls back to attribution


class PagedPipelinedBackend:
    """The shard_map pipelined decoder over *staged page pools*: the layer
    dim of each per-layer pool is split into stages exactly like the dense
    cache ([S, bps, N, KVH, Pg, D], pod-sharded stage dim), while block
    tables and seq_lens are replicated — so ``restage_cache`` migration on a
    live boundary swap moves per-layer pools between stages with the same
    composed gather as the dense layout, and in-flight paged KV survives a
    re-plan token-exactly."""

    migrates_cache = True

    def __init__(self, api, mesh, params, cfg: EngineConfig,
                 stage_blocks: Sequence[int], num_pages: int,
                 pages_per_slot: int, aot: Optional[AotRegistry] = None):
        self.api, self.mesh, self.params, self.cfg = api, mesh, params, cfg
        self.seg = api.model.segments[0]
        self.aot = aot or AotRegistry()
        self._layouts: Dict[Tuple[int, ...], Tuple] = {}
        self._restage: Dict[Tuple, Any] = {}    # (old, new) layout pair ->
        #                                         memoized jitted restage
        self._shape = (cfg.num_slots, num_pages, cfg.page_size,
                       pages_per_slot)
        # the kernel needs every mesh axis manual, which only use_kernel
        # arranges (runtime/pipeline.py)
        self.attn_path = "pallas" if cfg.use_kernel else "gather"
        self._build(stage_blocks)
        self.reset_state()

        def insert(staged, bt, sl, kk_st, vv_st, pages, offs, slot, bt_row,
                   seq_len):
            # kk_st, vv_st: [S, bps, KVH, S_pad, D] (stage-gathered layers);
            # pool index [:, :, pages, :, offs] puts the S_pad dim first.
            # pages may carry the out-of-range sentinel (num_pages) for
            # padding / COW-adopted shared pages -> mode="drop"
            k_pool, v_pool = staged
            k_pool = k_pool.at[:, :, pages, :, offs].set(
                kk_st.transpose(3, 0, 1, 2, 4), mode="drop")
            v_pool = v_pool.at[:, :, pages, :, offs].set(
                vv_st.transpose(3, 0, 1, 2, 4), mode="drop")
            return ((k_pool, v_pool), bt.at[slot].set(bt_row),
                    sl.at[slot].set(seq_len))

        def clear(staged, bt, sl, slot):
            return staged, bt.at[slot].set(0), sl.at[slot].set(0)

        def set_bt(bt, slot, idx, page):
            return bt.at[slot, idx].set(page)

        def copy_pg(staged, dst, src):
            # fork one physical page in every stage's per-layer pool
            k_pool, v_pool = staged
            return (k_pool.at[:, :, dst].set(k_pool[:, :, src]),
                    v_pool.at[:, :, dst].set(v_pool[:, :, src]))

        def commit(bt, sl, slot, bt_row, seq_len):
            return bt.at[slot].set(bt_row), sl.at[slot].set(seq_len)

        wrap = functools.partial(self.aot.wrap, dispatch="jit")
        self._insert = wrap("insert", insert)
        self._clear = wrap("clear_slot", clear)
        self._set_bt = wrap("set_table_entry", set_bt)
        self._copy_pg = wrap("copy_page", copy_pg)
        self._commit = wrap("commit_slot", commit)

    def _make_chunk(self, dec):
        """Chunked prefill against the STAGED pools: unstage -> run the
        stacked-layer chunk fn -> restage, all inside one jit (the gathers
        fuse with the chunk compute; page ids are layout-invariant so the
        host's block tables/refcounts are oblivious to staging, same
        contract as restage_cache)."""
        api, seg_name = self.api, self.seg.name
        S, bps, n = dec.num_stages, dec.bps, dec.seg.n
        if dec.uniform:
            def unstage(x):
                return x.reshape((n,) + x.shape[2:])
        else:
            sidx = dec._scatter_idx

            def unstage(x):
                return jnp.take(x.reshape((S * bps,) + x.shape[2:]),
                                jnp.asarray(sidx), axis=0)

        def chunk(params, staged, batch):
            stacked = jax.tree.map(unstage, staged)
            logits, new_pools = api.prefill_chunk_fn(
                params, {seg_name: stacked}, batch)
            return logits, dec._stage_tree(new_pools[seg_name])

        return chunk

    def _make_swapio(self, dec):
        """``gather_pages``/``scatter_pages`` over the STAGED pools (the
        two-tier swap transfer primitives): unstage → page gather → lossless
        seal fused in one jit for swap-out, and the inverse (unseal → stage
        → drop-scatter) for swap-in. Page ids are layout-invariant, so the
        host-side swap manifest is oblivious to staging — the same contract
        as restage_cache, and a manifest written under one stage layout
        restores correctly after a live boundary swap. The cipher runs
        replicated inside a shard_map over the whole mesh: its Pallas kernel
        cannot be partitioned automatically."""
        use_kernel = self.cfg.use_kernel
        S, bps, n = dec.num_stages, dec.bps, dec.seg.n

        def replicated(fn, **kw):
            return jax.shard_map(functools.partial(fn, use_kernel=use_kernel,
                                                   **kw),
                                 mesh=self.mesh, in_specs=P(), out_specs=P(),
                                 check_vma=False)
        if dec.uniform:
            def unstage(x):
                return x.reshape((n,) + x.shape[2:])
        else:
            sidx = dec._scatter_idx

            def unstage(x):
                return jnp.take(x.reshape((S * bps,) + x.shape[2:]),
                                jnp.asarray(sidx), axis=0)

        def gather(staged, pages, key, ctr):
            k_st, v_st = staged

            def sealed(pool_st, part):
                g = unstage(pool_st)[:, pages].transpose(1, 0, 2, 3, 4)
                g = g.reshape(pages.shape[0], -1)
                return replicated(sealing.seal_pages, part=part)(g, key, ctr)

            return sealed(k_st, 0), sealed(v_st, 1)

        def scatter(staged, ck, cv, pages, key, ctr):
            k_st, v_st = staged

            def restored(pool_st, c, part):
                rows = replicated(sealing.unseal_pages,
                                  out_dtype=pool_st.dtype, part=part)(
                                      c, key, ctr)
                g = rows.reshape(pages.shape[0], n, pool_st.shape[3],
                                 pool_st.shape[4], pool_st.shape[5])
                g_st = dec._stage_tree(g.transpose(1, 0, 2, 3, 4))
                return pool_st.at[:, :, pages].set(g_st, mode="drop")

            return (restored(k_st, ck, 0), restored(v_st, cv, 1))

        return gather, scatter

    def _build(self, stage_blocks: Sequence[int]) -> None:
        cfg = self.cfg
        self.stage_blocks = key = tuple(stage_blocks)
        hit = self._layouts.get(key)
        if hit is None:
            dec = PipelinedDecoder(
                self.api, self.mesh, num_stages=cfg.num_stages,
                num_microbatches=cfg.num_microbatches,
                seal_boundary=cfg.seal_boundary, use_kernel=cfg.use_kernel,
                stage_blocks=key)
            staged_params = dec.place_params(self.params)
            step_fn = self.aot.wrap(f"step{key}", dec.build(
                prestaged_params=True, paged=True), dispatch="jit")
            probe = self.aot.wrap(f"probe{key}",
                                  dec.build_stage_probe(paged=True),
                                  dispatch="jit")
            chunk_fn = self.aot.wrap(f"chunk{key}", self._make_chunk(dec),
                                     dispatch="jit")
            g_fn, s_fn = self._make_swapio(dec)
            gather_fn = self.aot.wrap(f"gather_pages{key}", g_fn,
                                      dispatch="jit")
            scatter_fn = self.aot.wrap(f"scatter_pages{key}", s_fn,
                                       dispatch="jit")
            hit = self._layouts[key] = (dec, staged_params, step_fn, probe,
                                        chunk_fn, gather_fn, scatter_fn)
        (self.dec, self.staged_params, self.step_fn, self._probe,
         self._chunk, self._gather, self._scatter) = hit
        self._probe_warm = False

    def reset_state(self) -> None:
        cache = self.api.init_paged_cache(*self._shape)
        staged = self.dec._stage_tree(cache[self.seg.name])
        self.state = (staged, *self.dec.place_replicated(
            (cache["block_tables"], cache["seq_lens"])))

    def step(self, tokens: jnp.ndarray, key) -> jnp.ndarray:
        logits, self.state = self.step_fn(self.staged_params, self.state,
                                          {"tokens": tokens}, key)
        return logits

    def prefill_chunk(self, toks, offset: int, chunk_len: int, bt_row,
                      pages, offs) -> jnp.ndarray:
        batch = {"tokens": toks, "offset": jnp.int32(offset),
                 "chunk_len": jnp.int32(chunk_len), "bt_row": bt_row,
                 "pages": pages, "offs": offs}
        staged, bt, sl = self.state
        logits, staged = self._chunk(self.params, staged, batch)
        self.state = (staged, bt, sl)
        return logits

    def commit_slot(self, slot: int, bt_row, seq_len: int) -> None:
        staged, bt, sl = self.state
        bt, sl = self._commit(bt, sl, jnp.int32(slot), bt_row,
                              jnp.int32(seq_len))
        self.state = (staged, bt, sl)

    def insert_slot(self, slot: int, kv, pages, offs, bt_row,
                    seq_len: int) -> None:
        kk, vv = kv                      # [L, KVH, S_pad, D]
        kk_st = self.dec._stage_tree(kk)
        vv_st = self.dec._stage_tree(vv)
        staged, bt, sl = self.state
        self.state = self._insert(staged, bt, sl, kk_st, vv_st, pages, offs,
                                  jnp.int32(slot), bt_row, jnp.int32(seq_len))

    def clear_slot(self, slot: int) -> None:
        staged, bt, sl = self.state
        self.state = self._clear(staged, bt, sl, jnp.int32(slot))

    def set_table_entry(self, slot: int, idx: int, page: int) -> None:
        staged, bt, sl = self.state
        self.state = (staged, self._set_bt(bt, jnp.int32(slot),
                                           jnp.int32(idx), jnp.int32(page)),
                      sl)

    def copy_page(self, dst: int, src: int) -> None:
        staged, bt, sl = self.state
        self.state = (self._copy_pg(staged, jnp.int32(dst), jnp.int32(src)),
                      bt, sl)

    def gather_pages(self, pages, key, ctr):
        """Seal ``pages`` out of the staged pools (0 = skip row). Returns
        (k_cipher, v_cipher); the caller fetches them to host."""
        staged, _bt, _sl = self.state
        return self._gather(staged, pages, key, ctr)

    def scatter_pages(self, ck, cv, pages, key, ctr) -> None:
        staged, bt, sl = self.state
        self.state = (self._scatter(staged, ck, cv, pages, key, ctr), bt, sl)

    def _restage_state(self, old_dec, old_key) -> None:
        """Same per-pair memoized restage as PipelinedDecodeBackend: the
        first occurrence of a layout pair AOT-warms the composed gather
        (one-off wall cost, off the stall ledger); every later swap across
        it dispatches from the memo, stall-free."""
        pair = (old_key, self.stage_blocks)
        fn = self._restage.get(pair)
        if fn is None:
            new_dec = self.dec
            fn = self.aot.wrap(
                f"restage{pair[0]}->{pair[1]}",
                lambda st: old_dec.restage_cache(st, new_dec),
                dispatch="jit")
            self._restage[pair] = fn
            self.state = fn.warm(self.state)
        else:
            self.state = fn(self.state)

    def swap(self, stage_blocks: Sequence[int]) -> bool:
        """Rebuild on the new boundaries and migrate the staged pools (the
        same composed unstage→restage gather as the dense layout, memoized
        per layout pair; block tables and seq_lens ride along unchanged)."""
        old_dec, old_key = self.dec, self.stage_blocks
        self._build(stage_blocks)
        self._restage_state(old_dec, old_key)
        return True

    def stage_times(self, repeats: int = 1) -> List[float]:
        from repro.models import layers as L
        cfg = self.cfg
        staged, bt, sl = self.state
        b_mb = cfg.num_slots // cfg.num_microbatches
        x = np.zeros((b_mb, 1, self.api.cfg.d_model), L.DEFAULT_DTYPE)
        bt, sl = np.asarray(bt[:b_mb]), np.asarray(sl[:b_mb])
        per_stage = []
        for s in range(cfg.num_stages):
            (blk_p, blk_c), dev = self.dec.stage_shard(
                (self.staged_params[self.seg.name], staged), s)
            args = (blk_p, blk_c, *jax.device_put(
                (self.dec._mask[s], x, bt, sl), dev))
            if not self._probe_warm:
                jax.block_until_ready(self._probe(*args))
            t0 = time.perf_counter()
            for _ in range(repeats):
                jax.block_until_ready(self._probe(*args))
            dt = (time.perf_counter() - t0) / repeats
            dt *= self.dec.stage_counts[s] / self.dec.bps
            per_stage.append(dt)
        self._probe_warm = True
        return per_stage


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class EngineEvent:
    step: int
    kind: str                  # admit | finish | replan | swap | swap_skipped
    detail: Any = None


@dataclasses.dataclass
class _ChunkState:
    """Host state of one slot's in-flight chunked prefill: the full token
    sequence being streamed in, the pages acquired so far (device block
    table stays unset until the final chunk commits), and the COW ledger —
    ``registered`` counts pages already frozen into the prefix index (a
    page is registered only once FULLY written, so another admission can
    never adopt a half-prefilled page)."""

    req: Request
    tokens: List[int]
    keys: List[tuple]                   # COW prefix keys, one per page
    t0: float                           # admission wall-clock start
    pos: int = 0                        # tokens prefilled so far
    chunks: int = 0
    pages: List[int] = dataclasses.field(default_factory=list)
    shared: List[bool] = dataclasses.field(default_factory=list)
    registered: int = 0
    logits: Any = None                  # last chunk's logits [1, V]


class ServingEngine:
    """Continuous-batching serving over the planner/pipeline/ft subsystems.

    ``launch/serve.py`` is a thin CLI over this class; tests drive it
    directly. The placement is a ``PlacementSpec`` (``self.spec``) from the
    segment-space solver — possibly non-prefix (untrusted segments
    interleaved mid-chain); segment s executes on pod s either way. Decoding
    is greedy argmax by default; ``EngineConfig.temperature``/``top_k``
    enable per-request-reproducible sampling (serving/sampling.py), which is
    token-equal to greedy at temperature 0.

    The KV cache is paged by default (``EngineConfig.kv_layout``): shared
    page pools + per-slot block tables, demand-grown ref-counted pages
    with COW prefix sharing and preemption (``page_policy="demand"``; see
    §Demand paging in DESIGN.md) or worst-case reservation at admission
    (``page_policy="reserve"``), recycling on completion, one-call
    batched prefill. Models
    without paged support (recurrent state, sliding windows, quantized
    caches) fall back to the legacy shared timeline, whose horizon is
    enforced by admission back-pressure instead of a mid-decode crash."""

    def __init__(self, api, mesh=None, rm: Optional[ResourceManager] = None,
                 config: Optional[EngineConfig] = None, params=None,
                 backend: Optional[str] = None):
        cfg = config or EngineConfig()
        assert pipeline_applicable(api), \
            f"{api.cfg.name}: serving needs a single homogeneous segment"
        assert cfg.num_slots % cfg.num_microbatches == 0
        assert cfg.kv_layout in ("paged", "timeline"), cfg.kv_layout
        # paged needs model support (dense/MoE/VLM, plain KV cache);
        # recurrent-state / SWA / quantized-cache models keep the timeline
        self.kv_layout = cfg.kv_layout if api.paged_ok else "timeline"
        if self.kv_layout == "timeline":
            assert cfg.prompt_capacity < cfg.max_seq
        self.api, self.mesh, self.config = api, mesh, cfg
        self.rm = rm or two_enclave_manager()
        self.params = params if params is not None \
            else api.init(jax.random.PRNGKey(0))

        # --- plan over the trust domains --------------------------------
        # min_stages: the serving mesh has a fixed pod count — ask the
        # solver for a placement that uses every pod (falls back when the
        # topology can't supply that many stages)
        self.profiles = profiles_from_arch(api.cfg, seq_len=1)
        self.replanner = OnlineReplanner(
            self.rm, self.profiles, n=cfg.plan_n, delta=cfg.delta,
            deviation_threshold=cfg.deviation_threshold, solver=cfg.solver,
            space=cfg.space, min_stages=cfg.num_stages)
        try:
            spec = self.replanner.plan()
        except InfeasibleError:
            self.replanner.min_stages = None
            spec = self.replanner.plan()
        self.spec = spec
        self.stage_blocks = self._blocks_from(spec)
        self.telemetry = StageTelemetry(
            self.replanner,
            monitor=HeartbeatMonitor(self.rm,
                                     timeout_s=cfg.heartbeat_timeout_s),
            interval=cfg.telemetry_interval)
        # per-engine AOT compile ledger; every jitted serving function is
        # registered here so warmup() can compile the full shape inventory
        # and stats() can report post-warmup compile stalls
        self.aot = AotRegistry()

        # --- paged KV page pool ------------------------------------------
        assert cfg.page_policy in ("demand", "reserve"), cfg.page_policy
        assert cfg.preempt_policy in ("auto", "swap", "recompute"), \
            cfg.preempt_policy
        assert cfg.disagg_role in ("", "prefill", "decode"), cfg.disagg_role
        # features that need a page pool fail HERE, by name, instead of deep
        # inside the pool on a layout that never built one
        if self.kv_layout != "paged":
            why = ("kv_layout='timeline' was requested" if api.paged_ok
                   else f"model '{api.cfg.name}' has no paged-cache support "
                        f"(sliding-window / quantized / recurrent cache)")
            if cfg.preempt_policy == "swap":
                raise ValueError(
                    f"preempt_policy='swap' requires the paged KV layout, "
                    f"but this engine runs the legacy timeline layout "
                    f"({why}): sealed page swap has no page pool to gather "
                    f"from. Use preempt_policy='auto' (resolves to "
                    f"'recompute' here) or 'recompute'.")
            if cfg.disagg_role:
                raise ValueError(
                    f"disagg_role='{cfg.disagg_role}' requires the paged KV "
                    f"layout, but this engine runs the legacy timeline "
                    f"layout ({why}): the prefill/decode handoff transfers "
                    f"sealed KV *pages* between page pools. Serve this "
                    f"model monolithically.")
        if cfg.disagg_role:
            assert cfg.page_policy == "demand", \
                "disaggregated serving needs demand paging (COW adoption " \
                "+ per-row allocation at transfer-in)"
        # "auto" resolves by layout; explicit values passed validation above
        self.preempt_policy = cfg.preempt_policy if \
            cfg.preempt_policy != "auto" else \
            ("swap" if self.kv_layout == "paged" else "recompute")
        if self.kv_layout == "paged":
            self.request_capacity = cfg.request_capacity or \
                (cfg.prompt_capacity + 64)
            assert self.request_capacity > cfg.prompt_capacity
            self.pages_per_slot = -(-self.request_capacity // cfg.page_size)
            num_pages = cfg.num_pages or \
                (cfg.num_slots * self.pages_per_slot + 1)
            self.pool = PagePool(num_pages, cfg.page_size)
            self.slot_pages: Dict[int, List[int]] = {}
            # host mirror of each active slot's device seq_len (= the next
            # decode write position); drives demand growth / fork decisions
            self.slot_len: Dict[int, int] = {}
        else:
            self.pool = None
        self.preemptions = 0
        self.peak_running = 0
        # two-tier swap: monotone per-engine swap sequence keys the cipher
        # keystream (no (key, counter) pair ever reused across swap events);
        # swap_fallbacks counts manifests dropped to break pin-deadlocks
        self._swap_seq = 0
        self.swap_fallbacks = 0
        # disaggregated handoff: transfer sequence numbers key the cipher
        # in the dedicated transfer counter space (sealing.transfer_seq),
        # so handoff seals never collide with swap or activation seals
        self._transfer_seq = 0
        self.transfers_out = 0
        # chaos fault plane + request-level recovery ladder (DESIGN.md
        # §Fault injection & recovery): every injected fault must land in
        # one of the named self.recovery counters or in self.failed as an
        # explicit per-request failure — never a silent drop or corrupt
        # token. pending_external is set by an orchestrator holding
        # in-flight handoff retries for this engine, so a head-of-line
        # stall while a retry is pending classifies as recoverable.
        self.faults = FaultPlane(cfg.faults) if cfg.faults is not None \
            else None
        self.recovery = self._fresh_recovery()
        self.failed: Dict[int, str] = {}
        self.pending_external = 0
        self.stall_reason: Optional[str] = None
        self._storm_pages: List[int] = []
        self._storm_left = 0
        self._death_pending = False
        self._stall_stage: Optional[int] = None

        # --- decode backend ----------------------------------------------
        if backend is None:
            backend = "pipelined" if (
                mesh is not None and cfg.num_stages > 1) else "local"
        if backend == "pipelined":
            assert mesh is not None, "pipelined backend needs a mesh"
            if self.kv_layout == "paged":
                self.backend = PagedPipelinedBackend(
                    api, mesh, self.params, cfg, self.stage_blocks,
                    self.pool.num_pages, self.pages_per_slot, aot=self.aot)
            else:
                self.backend = PipelinedDecodeBackend(
                    api, mesh, self.params, cfg, self.stage_blocks,
                    aot=self.aot)
        else:
            if self.kv_layout == "paged":
                self.backend = PagedLocalBackend(
                    api, self.params, cfg, self.stage_blocks,
                    self.pool.num_pages, self.pages_per_slot, aot=self.aot)
            else:
                self.backend = LocalDecodeBackend(api, self.params, cfg,
                                                  self.stage_blocks,
                                                  aot=self.aot)
        self.backend_kind = backend

        self.scheduler = SlotScheduler(cfg.num_slots,
                                       finished_cap=cfg.finished_cap)
        self.global_len = cfg.prompt_capacity
        self.pending = np.zeros(cfg.num_slots, np.int32)  # next input token
        self.steps = 0
        self.swaps = 0
        self.stalled = False            # head-of-line blocked, nothing active
        self._blocked_rid = None        # back-pressure event dedup
        # bounded: the paged engine runs indefinitely, so per-admission
        # history must not grow with lifetime (p50/p99 over a rolling
        # window; lifetime aggregates live in scheduler/telemetry totals)
        self.admission_ms: Deque[float] = deque(maxlen=cfg.admission_cap)
        self.admissions = 0
        self.prefill_calls = 0
        # events are a ring buffer; step() reports the CURRENT step's
        # events via _step_events, never by slicing the ring
        self.events: Deque[EngineEvent] = deque(maxlen=cfg.events_cap)
        self._step_events: List[EngineEvent] = []
        disp = "jit" if backend == "pipelined" else "compiled"
        self._prefill = self.aot.wrap("prefill_token", api.decode_fn,
                                      dispatch=disp)
        if self.kv_layout == "paged":
            self._prefill_at = self.aot.wrap(
                "prefill_bucket", api.prefill_at_fn, dispatch=disp)
        # packed prefill: K prompts share one bucketed call, logits read
        # per-row at each prompt's own last position (satellite of the
        # disaggregated prefill role, but usable monolithically too)
        assert cfg.prefill_pack >= 0, cfg.prefill_pack
        self._prefill_at_packed = None
        if (cfg.prefill_pack > 1 and self.kv_layout == "paged"
                and cfg.batched_prefill):
            self._prefill_at_packed = self.aot.wrap(
                "prefill_packed", api.prefill_packed_fn, dispatch=disp)
        self.packed_admissions = 0
        self.packed_prefills = 0
        self._key = jnp.uint32(0xC0FFEE)
        self.sampler = TokenSampler(cfg.temperature, cfg.top_k,
                                    cfg.sample_seed)
        # chunked prefill state: slot -> _ChunkState for every admitted
        # request whose prompt is still streaming in
        assert cfg.prefill_chunk >= 0, cfg.prefill_chunk
        self.chunking: Dict[int, _ChunkState] = {}
        self.chunked_admissions = 0
        self.chunk_steps = 0
        self.warmup_s = 0.0
        self.warmed = False
        self._in_warmup = False
        if cfg.warmup:
            self.warmup()

    # ------------------------------------------------------------------
    def _blocks_from(self, spec: PlacementSpec) -> Tuple[int, ...]:
        planned = spec.stage_sizes()
        n, S = self.api.model.segments[0].n, self.config.num_stages
        if len(planned) == S:
            return planned
        assert n % S == 0, \
            f"plan wants {len(planned)} stages, {n} blocks not even over {S}"
        return (n // S,) * S

    def _emit(self, kind: str, detail: Any = None) -> None:
        ev = EngineEvent(self.steps, kind, detail)
        self.events.append(ev)
        self._step_events.append(ev)

    # -- request API -------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               eos_id: Optional[int] = None) -> Request:
        assert 1 <= len(prompt) <= self.config.prompt_capacity, \
            f"prompt length {len(prompt)} > capacity " \
            f"{self.config.prompt_capacity}"
        if self.kv_layout == "paged":
            total = len(prompt) + max_new_tokens
            assert total <= self.request_capacity, \
                f"prompt+max_new {total} > request_capacity " \
                f"{self.request_capacity} (size EngineConfig." \
                f"request_capacity for longer generations)"
            if self.config.page_policy == "demand":
                # progress guarantee: after preempting every other slot the
                # request must fit with one page of fork headroom, or the
                # preemption loop could never free enough (DESIGN.md
                # §Demand paging)
                worst = self.pool.pages_needed(total) + 1
                assert worst <= self.pool.num_pages - 1, \
                    f"request needs {worst} pages (with fork headroom) but " \
                    f"the pool holds {self.pool.num_pages - 1}: demand " \
                    f"paging cannot guarantee progress; grow num_pages"
        return self.scheduler.submit(prompt, max_new_tokens, eos_id,
                                     step=self.steps)

    # -- admission gating: page-pool / timeline back-pressure --------------
    def _fits(self, req: Request) -> bool:
        """Can ``req`` be admitted *now*? False means the head of the queue
        waits — for resources that completions will free (pages, a slot),
        never for resources that can't come back (the legacy timeline)."""
        if self.kv_layout == "paged":
            if self.config.page_policy == "demand":
                if self.pool.has_transfer(req.rid):
                    # disaggregated handoff admission: one fresh page per
                    # sealed manifest row (+1 headroom) — rows resolved
                    # against this pool's COW index at ingest are pinned
                    # and re-adopt for free, exactly like swap resume
                    need, supply = self._transfer_budget(req)
                    return supply >= need
                if self.pool.has_swap(req.rid):
                    # swapped-out resume: needs one fresh device page per
                    # SEALED manifest row (+1 growth headroom) — shared
                    # rows re-adopt their pinned index pages for free
                    need, supply = self._swap_budget(req)
                    return supply >= need
                # demand paging admits on the *prompt's* pages (+1 headroom
                # for the first growth/fork), not the worst case — shared
                # prefix pages already resident in the COW index are free
                need, supply = self._page_budget(req)
                return supply >= need
            need = self.pool.pages_needed(len(req.prompt)
                                          + req.max_new_tokens)
            return self.pool.free_pages >= need
        # legacy shared timeline: admit only requests whose worst-case
        # generation finishes inside the horizon, so the engine back-
        # pressures at admission instead of crashing mid-decode
        return self.global_len + req.max_new_tokens <= self.config.max_seq

    def _prompt_tokens(self, req: Request) -> List[int]:
        """The token sequence a (possibly resumed) request prefills: the
        original prompt plus any tokens generated before a preemption —
        teacher-forcing the generated suffix reproduces the interrupted
        decode state token-exactly."""
        return list(req.prompt) + [int(t) for t in req.generated]

    def _prompt_page_keys(self, tokens: Sequence[int]) -> List[tuple]:
        """COW prefix-index keys, one per prompt page: page i is addressed
        by the *content* of every token it and its predecessors hold, so
        two requests share physical page i iff their prompts agree through
        the end of that page (a partial tail page only matches an equal-
        length equal-content tail)."""
        Pg = self.config.page_size
        P = len(tokens)
        n = self.pool.pages_needed(P)
        return [tuple(tokens[:min((i + 1) * Pg, P)]) for i in range(n)]

    def _page_budget(self, req: Request) -> Tuple[int, int]:
        """Demand admission budget: ``(need, supply)`` where need is the
        fresh (non-shared) prompt pages plus one page of growth/fork
        headroom, and supply is the free list plus index-only pages the
        allocator could evict — EXCLUDING pages this request's own prefix
        keys hit, which adoption is about to pin (counting them both as a
        hit and as evictable would over-admit).

        Chunked admission (prefill_chunk > 0 and a longer prompt) gates on
        the FIRST chunk's pages only: later chunks demand-allocate page by
        page, preempting younger slots when the pool runs dry — the
        submit-time worst-case assert still guarantees progress."""
        tokens = self._prompt_tokens(req)
        keys = self._prompt_page_keys(tokens)
        C = self.config.prefill_chunk
        if C > 0 and len(tokens) > C:
            keys = keys[:self.pool.pages_needed(C)]
        if self.config.prefix_sharing:
            hit_pages = {self.pool.prefix_index[k] for k in keys
                         if k in self.pool.prefix_index}
            fresh = sum(1 for k in keys
                        if k not in self.pool.prefix_index)
        else:
            hit_pages, fresh = set(), len(keys)
        supply = self.pool.free_pages + sum(
            1 for p in self.pool.prefix_index.values()
            if self.pool.refcount[p] == 1 and p not in hit_pages)
        return fresh + 1, supply

    def _swap_budget(self, req: Request) -> Tuple[int, int]:
        """Resume budget for a swapped-out request: one fresh page per
        sealed manifest row plus one page of growth/fork headroom; supply
        is free + evictable pages (manifest-pinned shared pages hold
        refcount >= 2, so they are never counted as evictable)."""
        man = self.pool.manifest(req.rid)
        supply = self.pool.free_pages + self.pool.evictable_pages
        return man.sealed_pages + 1, supply

    def _transfer_budget(self, req: Request) -> Tuple[int, int]:
        """Admission budget for an ingested handoff: same shape as
        ``_swap_budget`` — one fresh page per sealed row plus growth/fork
        headroom; shared (COW-resolved) rows are pinned and free."""
        man = self.pool.transfer_manifest[req.rid]
        supply = self.pool.free_pages + self.pool.evictable_pages
        return man.sealed_pages + 1, supply

    def _bucket(self, n: int) -> int:
        """Pad prompt lengths to power-of-two buckets (capped at
        prompt_capacity — or request_capacity for prompts a preemption
        extended past it) so batched prefill compiles O(log capacity)
        shapes, not one per distinct prompt length."""
        b = 4
        while b < n:
            b *= 2
        cap = self.config.prompt_capacity
        if self.kv_layout == "paged" and n > cap:
            cap = self.request_capacity
        return min(b, cap)

    # -- admission: prefill into a free slot -------------------------------
    def _prefill_slot(self, slot: int, req: Request) -> None:
        t0 = time.perf_counter()
        if self.kv_layout == "paged":
            if self.pool.has_transfer(req.rid):
                # disaggregated handoff: restore the peer-sealed pages in
                # one warmed scatter — no prefill, no logits, no sample
                # (the prefill engine already sampled the first token).
                # A payload that fails integrity verification drops the
                # manifest and falls through to teacher-forced re-prefill
                # (prompt + the first token the prefill role sampled).
                if self._transfer_in(slot, req, t0):
                    return
            elif self.pool.has_swap(req.rid):
                # two-tier resume: restore the sealed pages instead of
                # re-prefilling — no logits, no new token (the token the
                # victim sampled just before preemption rides along in
                # req.generated and becomes the next decode input). A
                # tampered payload drops the manifest and falls through
                # to the recompute path below, which rebuilds the same KV
                # bit-identically from prompt + generated.
                if self._swap_in(slot, req, t0):
                    return
            C = self.config.prefill_chunk
            if C > 0 and len(self._prompt_tokens(req)) > C:
                self._begin_chunked(slot, req, t0)
                return
            logits, shared = self._prefill_paged(slot, req)
            detail = {"rid": req.rid, "slot": slot,
                      "pages": len(self.slot_pages[slot]), "shared": shared}
            if req.generated:
                detail["resumed_at"] = len(req.generated)
        else:
            logits = self._prefill_timeline(slot, req)
            detail = {"rid": req.rid, "slot": slot,
                      "start": self.global_len - len(req.prompt)}
        # a resumed request's first sample continues its keystream at
        # len(generated) — at temperature 0 this is the same argmax the
        # interrupted decode step would have taken (teacher forcing)
        with self.telemetry.span("serve.device_wait"):
            first = self.sampler.sample_one(logits, req.rid,
                                            len(req.generated))
        self.pending[slot] = first
        detail["ms"] = (time.perf_counter() - t0) * 1e3
        self.admission_ms.append(detail["ms"])
        self.admissions += 1
        self._emit("admit", detail)
        fin = self.scheduler.on_token(slot, first, step=self.steps)
        if fin is not None:
            self._on_finish(fin)

    def _prefill_timeline(self, slot: int, req: Request):
        """Legacy offset prefill: one decode step per prompt token, ending
        at the shared-timeline tip, with a per-slot ``start`` mask."""
        P = len(req.prompt)
        start = self.global_len - P          # prompt ends at the timeline tip
        assert start >= 0
        cache = self.api.init_cache(1, self.config.max_seq)
        cache["len"] = jnp.int32(start)
        cache["start"] = jnp.full((1,), start, jnp.int32)
        logits = None
        for t in req.prompt:
            tok = jnp.full((1, 1), t, jnp.int32)
            logits, cache = self._prefill(self.params, cache, {"tokens": tok})
            self.prefill_calls += 1
        self.backend.insert_slot(slot, cache)
        return logits

    def _acquire_pages(self, req: Request) -> Tuple[List[int], List[bool]]:
        """Admission-time page acquisition.

        ``reserve``: worst-case pages for prompt+max_new, all private.
        ``demand``: one page per *prompt* page only; with prefix sharing,
        pages whose content key is already in the COW index are adopted by
        reference (incref, no prefill scatter) instead of allocated."""
        tokens = self._prompt_tokens(req)
        P = len(tokens)
        if self.config.page_policy == "reserve":
            need = self.pool.pages_needed(
                len(req.prompt) + req.max_new_tokens)
            pages = self.pool.alloc(need)
            assert pages is not None, "gated by _fits"
            return pages, [False] * need
        keys = self._prompt_page_keys(tokens)
        pages: List[Optional[int]] = [None] * len(keys)
        shared = [False] * len(keys)
        # adopt every index hit FIRST: the incref pins those pages, so the
        # fresh allocations below can never evict a page a later key of
        # this same admission would have shared
        if self.config.prefix_sharing:
            for i, key in enumerate(keys):
                pg = self.pool.lookup_prefix(key)
                if pg is not None:
                    pages[i], shared[i] = pg, True
        for i, key in enumerate(keys):
            if pages[i] is None:
                pg = self.pool.alloc_one()
                assert pg is not None, "gated by _fits"
                if self.config.prefix_sharing:
                    self.pool.register_prefix(key, pg)
                pages[i] = pg
        return pages, shared

    def _prefill_paged(self, slot: int, req: Request):
        """Paged admission: acquire the slot's pages (worst-case under
        ``reserve``, prompt-only + COW adoption under ``demand``), prefill
        the whole prompt in ONE jitted call (right-padded to a bucket), and
        scatter the first P positions into the slot's pages — positions in
        shared (adopted) pages and right-padding scatter to the
        out-of-range drop sentinel, so physical shared pages are written
        exactly once, by their first owner. Positions are 0-based per
        request. A preempted request resumes here with its generated
        tokens appended to the prompt (teacher forcing). Returns
        ``(logits, shared_page_count)``."""
        tokens = self._prompt_tokens(req)
        P = len(tokens)
        pages, shared = self._acquire_pages(req)
        self.slot_pages[slot] = pages
        self.slot_len[slot] = P
        bt_row = np.zeros(self.pages_per_slot, np.int32)
        bt_row[:len(pages)] = pages
        seg = self.api.model.segments[0].name
        S_pad = self._bucket(P)
        if self.config.batched_prefill:
            toks = np.zeros((1, S_pad), np.int32)
            toks[0, :P] = tokens
            logits, caches = self._prefill_at(
                self.params, {"tokens": jnp.asarray(toks),
                              "prompt_len": jnp.int32(P)})
            kk, vv = caches[seg]
            kk, vv = kk[:, 0], vv[:, 0]          # [L, KVH, S_pad, D]
            self.prefill_calls += 1
        else:
            cache = self.api.init_cache(1, S_pad)
            logits = None
            for t in tokens:
                tok = jnp.full((1, 1), t, jnp.int32)
                logits, cache = self._prefill(self.params, cache,
                                              {"tokens": tok})
                self.prefill_calls += 1
            kk, vv = cache[seg]
            kk, vv = kk[:, 0, :, :S_pad], vv[:, 0, :, :S_pad]
        # positions >= P (right padding) and positions in adopted shared
        # pages scatter to index num_pages: out of range, dropped by the
        # backend's mode="drop" scatter (never page 0 — the null page
        # stays all-zero, a device-checkable invariant)
        Pg, N = self.config.page_size, self.pool.num_pages
        idx = np.arange(S_pad)
        page_of = np.minimum(idx, P - 1) // Pg
        shared_of = np.asarray(shared, bool)[page_of]
        skip = (idx >= P) | shared_of
        pages_vec = np.where(skip, N,
                             np.asarray(pages, np.int32)[page_of])
        offs_vec = np.where(idx < P, idx % Pg, 0).astype(np.int32)
        self.backend.insert_slot(slot, (kk, vv),
                                 jnp.asarray(pages_vec.astype(np.int32)),
                                 jnp.asarray(offs_vec), jnp.asarray(bt_row),
                                 P)
        return logits, int(sum(shared))

    # -- chunked prefill: stream a long prompt in over many steps ----------
    def _begin_chunked(self, slot: int, req: Request, t0: float) -> None:
        """Admit ``req`` into ``slot`` WITHOUT prefilling: the prompt's KV
        streams in one fixed-size chunk per engine step (_advance_chunks),
        interleaved with the batch's decode ticks — a long prompt costs
        its batch-mates at most one chunk of extra latency per token
        instead of a whole-prompt admission stall. Until the final chunk
        commits the block table, the device row stays idle (seq_len 0,
        decode writes drop on the null page) and the request sits in
        PREFILL state: it owns pages and can be preempted, but produces
        no tokens and takes no decode batch work."""
        tokens = self._prompt_tokens(req)
        cs = _ChunkState(req=req, tokens=tokens,
                         keys=self._prompt_page_keys(tokens), t0=t0)
        if self.config.page_policy == "reserve":
            need = self.pool.pages_needed(
                len(req.prompt) + req.max_new_tokens)
            pages = self.pool.alloc(need)
            assert pages is not None, "gated by _fits"
            cs.pages, cs.shared = pages, [False] * need
        self.slot_pages[slot] = cs.pages
        self.slot_len[slot] = 0
        self.chunking[slot] = cs
        self.scheduler.mark_prefill(slot)
        self._emit("chunk_admit",
                   {"rid": req.rid, "slot": slot, "prompt": len(tokens),
                    "chunk": self.config.prefill_chunk})

    def _advance_chunks(self) -> None:
        """One chunk of ONE in-flight prompt per engine step (oldest rid
        first — FIFO fairness), scheduled before the decode tick."""
        if not self.chunking:
            return
        slot = min(self.chunking, key=lambda s: self.chunking[s].req.rid)
        cs = self.chunking[slot]
        with self.telemetry.span("serve.chunk", rid=cs.req.rid):
            self._run_chunk(slot, cs)
            # _run_chunk may have preempted the slot mid-acquisition
            if slot in self.chunking and cs.pos == len(cs.tokens):
                self._finish_chunked(slot, cs)

    def _run_chunk(self, slot: int, cs: _ChunkState) -> None:
        cfg = self.config
        req = cs.req
        C, Pg, N = cfg.prefill_chunk, cfg.page_size, self.pool.num_pages
        P, pos = len(cs.tokens), cs.pos
        end = min(pos + C, P)
        if cfg.page_policy == "demand":
            # acquire pages covering [0, end): COW index hits adopt the
            # frozen page by reference; misses demand-allocate, preempting
            # the youngest slot when the pool runs dry — possibly US
            while len(cs.pages) * Pg < end:
                i = len(cs.pages)
                pg, sh = None, False
                if cfg.prefix_sharing:
                    pg = self.pool.lookup_prefix(cs.keys[i])
                    sh = pg is not None
                if pg is None:
                    pg = self._alloc_or_preempt(req)
                    if pg is None:
                        return          # req itself was preempted; requeued
                cs.pages.append(pg)     # slot_pages aliases this list
                cs.shared.append(sh)
        Cp = end - pos
        toks = np.zeros((1, C), np.int32)
        toks[0, :Cp] = cs.tokens[pos:end]
        # scatter targets for the chunk's own KV: positions >= Cp (right
        # padding) and positions in adopted shared pages go to the
        # out-of-range drop sentinel, exactly like one-shot admission
        idx = np.arange(C)
        abs_pos = pos + idx
        page_of = np.minimum(abs_pos, end - 1) // Pg
        shared_of = np.asarray(cs.shared, bool)[page_of]
        skip = (idx >= Cp) | shared_of
        pages_vec = np.where(skip, N,
                             np.asarray(cs.pages, np.int32)[page_of])
        offs_vec = np.where(idx < Cp, abs_pos % Pg, 0).astype(np.int32)
        bt_row = np.zeros((1, self.pages_per_slot), np.int32)
        bt_row[0, :len(cs.pages)] = cs.pages
        cs.logits = self.backend.prefill_chunk(
            jnp.asarray(toks), pos, Cp, jnp.asarray(bt_row),
            jnp.asarray(pages_vec.astype(np.int32)), jnp.asarray(offs_vec))
        self.prefill_calls += 1
        self.chunk_steps += 1
        cs.pos, cs.chunks = end, cs.chunks + 1
        if cfg.page_policy == "demand" and cfg.prefix_sharing:
            # freeze pages into the COW index only once FULLY written —
            # a half-prefilled page must never be adoptable
            while cs.registered < len(cs.pages):
                i = cs.registered
                if cs.pos < min((i + 1) * Pg, P):
                    break
                if not cs.shared[i]:
                    self.pool.register_prefix(cs.keys[i], cs.pages[i])
                cs.registered += 1
        self._emit("chunk", {"rid": req.rid, "slot": slot,
                             "pos": cs.pos, "of": P})

    def _finish_chunked(self, slot: int, cs: _ChunkState) -> None:
        """Last chunk landed: commit the block table + seq_len (the row
        joins the decode batch), sample the first token from the final
        chunk's logits — the same logits position one-shot prefill reads —
        and flip the request to RUNNING."""
        req = cs.req
        P = len(cs.tokens)
        bt_row = np.zeros(self.pages_per_slot, np.int32)
        bt_row[:len(cs.pages)] = cs.pages
        self.backend.commit_slot(slot, jnp.asarray(bt_row), P)
        self.slot_len[slot] = P
        del self.chunking[slot]
        self.scheduler.mark_running(slot)
        with self.telemetry.span("serve.device_wait"):
            first = self.sampler.sample_one(cs.logits, req.rid,
                                            len(req.generated))
        self.pending[slot] = first
        ms = (time.perf_counter() - cs.t0) * 1e3
        self.admission_ms.append(ms)
        self.admissions += 1
        self.chunked_admissions += 1
        self._emit("admit", {"rid": req.rid, "slot": slot,
                             "pages": len(cs.pages),
                             "shared": int(sum(cs.shared)),
                             "chunks": cs.chunks, "ms": ms})
        fin = self.scheduler.on_token(slot, first, step=self.steps)
        if fin is not None:
            self._on_finish(fin)

    def _on_finish(self, fin: Request) -> None:
        self._emit("finish", {"rid": fin.rid, "by": fin.finished_by})
        if self.kv_layout == "paged" and fin.slot in self.slot_pages:
            # release() decrefs: pages shared with other slots or frozen in
            # the COW index survive until their last reference drops
            self.pool.release(self.slot_pages.pop(fin.slot))
            self.slot_len.pop(fin.slot, None)
            self.backend.clear_slot(fin.slot)

    # -- demand paging: preemption + per-step growth/fork ------------------
    def _preempt(self, slot: int, req: Request) -> None:
        """Evict ``req`` from its slot to reclaim pages. Under
        ``preempt_policy="swap"`` a RUNNING victim's private pages are
        sealed to the host swap tier first (resume is then O(pages), not
        O(recompute)); mid-chunked-prefill victims and the ``"recompute"``
        oracle discard their KV — the generated tokens requeue as a prompt
        extension and re-prefill teacher-forced. Either way the request
        goes to the FRONT of the queue (victims were admitted before
        anything still queued, so appendleft keeps the queue rid-ordered)
        and the resumed stream is bit-identical."""
        if (self.preempt_policy == "swap"
                and self.config.page_policy == "demand"
                and req.status == RUNNING and slot not in self.chunking):
            self._preempt_swap(slot, req)
            return
        req.preemptions += 1
        self.preemptions += 1
        cs = self.chunking.pop(slot, None)
        self.pool.release(self.slot_pages.pop(slot))
        self.slot_len.pop(slot)
        self.backend.clear_slot(slot)
        self.scheduler.preempt(slot)
        self.pending[slot] = 0
        detail = {"rid": req.rid, "slot": slot,
                  "generated": len(req.generated)}
        if cs is not None:
            # mid-chunked-prefill eviction: the KV written so far is
            # dropped with the pages; re-admission restarts the chunk
            # stream from token 0 (registered prefix pages survive in the
            # COW index, so the retry usually adopts them back for free)
            detail["mid_prefill"] = True
            detail["prefilled"] = cs.pos
        if self._storm_pages:
            self.recovery["storm_preemptions"] += 1
        self._emit("preempt", detail)

    def _preempt_swap(self, slot: int, req: Request) -> None:
        """Two-tier eviction: seal the slot's PRIVATE pages (refcount 1)
        into host buffers through the lossless bit-cipher and record a swap
        manifest; COW-shared pages (refcount > 1 — necessarily frozen in
        the prefix index, since eviction requires refcount == 1) are never
        spilled: the manifest pins them in place and swap-in re-adopts
        them. The gather uses a fixed-shape [pages_per_slot] page vector
        (0 = null page for shared/pad rows), so one warmed executable
        covers every swap."""
        assert req.generated, "RUNNING victim must hold a sampled token"
        req.preemptions += 1
        self.preemptions += 1
        pages = self.slot_pages.pop(slot)
        n_tokens = self.slot_len.pop(slot)
        MP = self.pages_per_slot
        entries: List[Tuple[str, Any]] = []
        gather_vec = np.zeros(MP, np.int32)
        for i, pg in enumerate(pages):
            if self.pool.refcount[pg] > 1:
                key = self.pool._page_key.get(pg)
                assert key is not None, \
                    f"shared page {pg} missing from the prefix index"
                entries.append(("shared", (key, pg)))
            else:
                entries.append(("sealed", i))
                gather_vec[i] = pg
        seq = self._swap_seq
        self._swap_seq += 1
        ck, cv = self.backend.gather_pages(
            jnp.asarray(gather_vec), self._key, jnp.uint32(seq))
        # fetch to host: the swap tier is host memory — device pages free
        # the moment release() drops their last reference below
        payload = (np.asarray(ck), np.asarray(cv))
        # integrity tag over the sealed bits: the XOR page cipher is
        # malleable, so swap-in verifies this digest before adopting the
        # unsealed rows (the re-hash overlaps the async scatter dispatch)
        self.pool.swap_out(req.rid, entries, payload, n_tokens, seq,
                           digest=sealing.payload_digest(payload))
        self.pool.release(pages)        # manifest pins outlive slot refs
        self.backend.clear_slot(slot)
        self.scheduler.preempt(slot, swapped=True)
        self.pending[slot] = 0
        if self._storm_pages:
            self.recovery["storm_preemptions"] += 1
        self._emit("preempt", {
            "rid": req.rid, "slot": slot, "policy": "swap",
            "generated": len(req.generated),
            "sealed_pages": sum(1 for t, _ in entries if t == "sealed"),
            "shared_pages": sum(1 for t, _ in entries if t == "shared")})

    def _integrity_reject(self, req: Request, path: str,
                          fresh: List[int], e: Exception) -> bool:
        """Common failure arm for both verification phases of swap-in and
        transfer-in: return any freshly allocated pages (whose scattered
        contents, if the dispatch already ran, no block table will ever
        reference), drop the tampered manifest, and count the fallback —
        the caller reverts to teacher-forced recompute/re-prefill."""
        self.pool.release(fresh)
        if path == "swap":
            self.pool.drop_swap(req.rid)
        else:
            self.pool.drop_transfer(req.rid)
        self.recovery[f"unseal_fallback_{path}"] += 1
        self._emit("unseal_fallback", {"rid": req.rid, "path": path,
                                       "error": str(e)})
        return False

    def _swap_in(self, slot: int, req: Request, t0: float) -> bool:
        """Resume a swapped-out request: allocate one fresh device page per
        sealed manifest row, unseal+scatter the host payload into them in
        one warmed call, re-adopt shared pages in place (the manifest's pin
        reference transfers to the slot's block table), and rebuild the
        block table at the saved seq_len. No recompute, no logits, no new
        sample: the pre-preemption token (generated[-1]) was never written
        to KV — it is the next decode input, exactly as in the undisturbed
        run, so the stream continues bit-identically.

        Returns False when the payload fails integrity verification (the
        fault plane's tamper site, or a real man-in-the-middle on the host
        swap tier): the manifest is dropped and the caller falls back to
        teacher-forced recompute prefill — the same KV is rebuilt from
        prompt + generated, so the stream is still bit-identical.

        The unseal+scatter is dispatched BEFORE the host-side digest check:
        XLA dispatch is asynchronous, so the device unseals while the host
        re-hashes the sealed bits, hiding the verification cost behind
        device work instead of adding it to the resume latency. Nothing is
        adopted until the digest matches — the block table only commits
        after verification, and on a mismatch the freshly allocated pages
        are released before any table references them, so the scattered
        plaintext of a tampered payload is unreachable garbage."""
        man = self.pool.swap_manifest[req.rid]
        if self.faults is not None and not self._in_warmup:
            tampered, mode = self.faults.maybe_tamper_swap(man.payload)
            if mode is not None:
                man.payload = tampered
                self._emit("fault_tamper", {"rid": req.rid, "path": "swap",
                                            "mode": mode})
        try:
            sealing.verify_structure(man.payload, man.digest,
                                     context=f"swap-in rid {req.rid}")
        except sealing.SealIntegrityError as e:
            return self._integrity_reject(req, "swap", [], e)
        MP, N = self.pages_per_slot, self.pool.num_pages
        pages: List[int] = []
        fresh: List[int] = []
        scatter_vec = np.full(MP, N, np.int32)
        restored = 0
        for i, (tag, val) in enumerate(man.entries):
            if tag == "shared":
                pages.append(val[1])
            else:
                pg = self.pool.alloc_one()
                assert pg is not None, "gated by _fits/_swap_budget"
                pages.append(pg)
                fresh.append(pg)
                scatter_vec[i] = pg
                restored += 1
        ck, cv = man.payload
        self.backend.scatter_pages(
            jnp.asarray(ck), jnp.asarray(cv), jnp.asarray(scatter_vec),
            self._key, jnp.uint32(man.counter))
        try:
            sealing.verify_payload(man.payload, man.digest,
                                   context=f"swap-in rid {req.rid}")
        except sealing.SealIntegrityError as e:
            return self._integrity_reject(req, "swap", fresh, e)
        man = self.pool.swap_in(req.rid)
        bt_row = np.zeros(MP, np.int32)
        bt_row[:len(pages)] = pages
        self.backend.commit_slot(slot, jnp.asarray(bt_row), man.n_tokens)
        self.slot_pages[slot] = pages
        self.slot_len[slot] = man.n_tokens
        self.pending[slot] = req.generated[-1]
        ms = (time.perf_counter() - t0) * 1e3
        self.admission_ms.append(ms)
        self.admissions += 1
        self._emit("admit", {"rid": req.rid, "slot": slot,
                             "resumed": "swap", "pages": len(pages),
                             "restored": restored,
                             "shared": len(pages) - restored, "ms": ms})
        return True

    # -- disaggregated handoff: sealed cross-engine KV transfer ------------
    def export_transfer(self, slot: int) -> Tuple[Request, "TransferManifest"]:
        """Prefill-side handoff: seal EVERY page of ``slot`` (shared pages
        included — the payload keeps all rows, so decode-side pin demotion
        is lossless) in one warmed ``gather_pages`` call keyed by a counter
        from the dedicated transfer sequence space, free this engine's
        pages, and vacate the slot (HANDOFF state). Returns the request and
        the manifest the orchestrator ships to the decode engine. The first
        sampled token rides in ``req.generated`` and was never written to
        KV — it becomes the decode engine's first input, exactly as the
        pre-preemption token does at swap-in."""
        assert self.kv_layout == "paged" \
            and self.config.page_policy == "demand"
        req = self.scheduler.slots[slot]
        assert req is not None and req.status == RUNNING and req.generated, \
            (slot, req)
        pages = self.slot_pages.pop(slot)
        n_tokens = self.slot_len.pop(slot)
        # content keys for the decode pool's COW resolution: the tokens
        # whose KV is actually written (generated[-1] is pending, unwritten)
        tokens = list(req.prompt) + [int(t) for t in req.generated[:-1]]
        assert len(tokens) == n_tokens, (len(tokens), n_tokens)
        keys = self._prompt_page_keys(tokens)
        MP = self.pages_per_slot
        gather_vec = np.zeros(MP, np.int32)
        entries: List[Tuple[str, Any]] = []
        for i, pg in enumerate(pages):
            gather_vec[i] = pg
            entries.append(("sealed",
                            (i, keys[i] if i < len(keys) else None)))
        seq = sealing.transfer_seq(self._transfer_seq)
        self._transfer_seq += 1
        ck, cv = self.backend.gather_pages(
            jnp.asarray(gather_vec), self._key, jnp.uint32(seq))
        payload = (np.asarray(ck), np.asarray(cv))
        man = TransferManifest(req.rid, n_tokens, entries, payload, seq,
                               sealing.payload_digest(payload))
        self.pool.release(pages)
        self.backend.clear_slot(slot)
        self.scheduler.handoff(slot, step=self.steps)
        self.pending[slot] = 0
        self.transfers_out += 1
        self._emit("handoff_out", {"rid": req.rid, "slot": slot,
                                   "pages": len(pages),
                                   "n_tokens": n_tokens})
        return req, man

    def ingest_transfer(self, req: Request, man: "TransferManifest") -> None:
        """Decode-side handoff ingestion: resolve each keyed sealed row
        against THIS pool's COW prefix index (hits flip to pinned shared
        entries — their payload rows will scatter to the drop sentinel),
        park the manifest, and adopt the request into the admission queue.
        ``_fits`` then gates on the remaining sealed rows and
        ``_prefill_slot`` routes to ``_transfer_in``."""
        assert self.kv_layout == "paged" \
            and self.config.page_policy == "demand"
        total = man.n_tokens + (req.max_new_tokens - len(req.generated)) + 1
        assert total <= self.request_capacity, \
            f"handoff rid {req.rid}: {total} tokens > decode " \
            f"request_capacity {self.request_capacity}"
        assert self.pool.pages_needed(total) + 1 <= self.pool.num_pages - 1, \
            f"handoff rid {req.rid} cannot fit the decode pool"
        entries = list(man.entries)
        adopted = 0
        if self.config.prefix_sharing:
            for i, (tag, val) in enumerate(entries):
                assert tag == "sealed", (i, tag)
                _row, key = val
                if key is None:
                    continue
                pg = self.pool.lookup_prefix(key)
                if pg is not None:      # the lookup pinned pg (manifest ref)
                    entries[i] = ("shared", (key, pg))
                    adopted += 1
        self.pool.register_transfer(req.rid, entries, man.payload,
                                    man.n_tokens, man.counter,
                                    digest=man.digest)
        self.scheduler.adopt(req)
        self._emit("handoff_in", {"rid": req.rid,
                                  "sealed": len(entries) - adopted,
                                  "shared": adopted})

    def _transfer_in(self, slot: int, req: Request, t0: float) -> bool:
        """Admit an ingested handoff: allocate one fresh device page per
        sealed row, unseal+scatter the peer's payload in ONE warmed call
        (the same ``scatter_pages`` executable swap-in uses — the counter
        is a traced argument), adopt COW-resolved shared pages in place,
        rebuild the block table at the transferred seq_len, and register
        freshly landed prompt pages in this pool's prefix index (the same
        freezing one-shot admission performs). No sample: the prefill
        engine's first token (generated[-1]) is the next decode input, so
        the stream continues bit-identically to the monolithic engine.

        Returns False when the payload fails integrity verification (a
        handoff corrupted or truncated in transit): the manifest is
        dropped and the caller falls back to teacher-forced re-prefill of
        prompt + the prefill role's first token — still bit-identical.

        Same dispatch-then-verify overlap as ``_swap_in``: the scatter is
        dispatched asynchronously, the host re-hashes the sealed bits while
        the device unseals, and the block table only commits after the
        digest matches — a tampered handoff's scattered plaintext lands in
        pages that are released before anything references them."""
        man = self.pool.transfer_manifest[req.rid]
        try:
            sealing.verify_structure(man.payload, man.digest,
                                     context=f"transfer-in rid {req.rid}")
        except sealing.SealIntegrityError as e:
            return self._integrity_reject(req, "transfer", [], e)
        MP, N = self.pages_per_slot, self.pool.num_pages
        pages: List[int] = []
        fresh: List[int] = []
        scatter_vec = np.full(MP, N, np.int32)
        fresh_keys: List[Tuple[tuple, int]] = []
        restored = 0
        for i, (tag, val) in enumerate(man.entries):
            if tag == "shared":
                pages.append(val[1])
            else:
                row, key = val
                pg = self.pool.alloc_one()
                assert pg is not None, "gated by _fits/_transfer_budget"
                pages.append(pg)
                fresh.append(pg)
                scatter_vec[row] = pg
                restored += 1
                if key is not None:
                    fresh_keys.append((key, pg))
        ck, cv = man.payload
        self.backend.scatter_pages(
            jnp.asarray(ck), jnp.asarray(cv), jnp.asarray(scatter_vec),
            self._key, jnp.uint32(man.counter))
        try:
            sealing.verify_payload(man.payload, man.digest,
                                   context=f"transfer-in rid {req.rid}")
        except sealing.SealIntegrityError as e:
            return self._integrity_reject(req, "transfer", fresh, e)
        man = self.pool.transfer_in(req.rid)
        bt_row = np.zeros(MP, np.int32)
        bt_row[:len(pages)] = pages
        self.backend.commit_slot(slot, jnp.asarray(bt_row), man.n_tokens)
        self.slot_pages[slot] = pages
        self.slot_len[slot] = man.n_tokens
        if self.config.prefix_sharing:
            for key, pg in fresh_keys:
                if key not in self.pool.prefix_index:
                    self.pool.register_prefix(key, pg)
        self.pending[slot] = req.generated[-1]
        ms = (time.perf_counter() - t0) * 1e3
        self.admission_ms.append(ms)
        self.admissions += 1
        self._emit("admit", {"rid": req.rid, "slot": slot,
                             "resumed": "transfer", "pages": len(pages),
                             "restored": restored,
                             "shared": len(pages) - restored, "ms": ms})
        return True

    def _maybe_break_swap_deadlock(self, nxt: Request) -> bool:
        """Pin-deadlock breaker: with nothing active and nothing chunking,
        no completion will ever free pages — only swap-/transfer-manifest
        pins and the (evictable) COW index hold them. First demote
        transfer-manifest pins (LOSSLESS: the handoff payload retains every
        row, so shared entries flip back to sealed and admission scatters
        them from the payload instead of adopting index pages), then drop
        swap manifests youngest-first (the head's own manifest last) until
        the head fits; each dropped swap reverts to the recompute oracle
        (its sealed payload is discarded, its shared pins released),
        restoring PR 6's progress guarantee. Returns True when the head
        now fits."""
        if self.kv_layout != "paged":
            return False
        if self.scheduler.active() or self.chunking:
            return False                # completions can still free pages
        if self._storm_pages:
            # an injected pool-exhaustion storm wedged admission with
            # nothing left to complete: reclaim the seized pages before
            # sacrificing any manifest (the storm is transient noise;
            # manifests are requests' KV)
            self._release_storm(reason="deadlock")
            self.recovery["storm_reclaims"] += 1
            if self._fits(nxt):
                return True
        if not (self.pool.swap_manifest or self.pool.transfer_manifest):
            return False
        for rid in sorted(self.pool.transfer_manifest):
            if self._fits(nxt):
                break
            if self.pool.demote_transfer(rid):
                self._emit("transfer_demote", {"rid": rid})
        while not self._fits(nxt) and self.pool.swap_manifest:
            others = sorted(r for r in self.pool.swap_manifest
                            if r != nxt.rid)
            rid = others[-1] if others else nxt.rid
            self.pool.drop_swap(rid)
            self.swap_fallbacks += 1
            for q in self.scheduler.queue:
                if q.rid == rid:
                    q.status = QUEUED   # back to the recompute resume path
            self._emit("swap_fallback", {"rid": rid})
        return self._fits(nxt)

    # -- chaos fault plane: injection ticks + recovery ladder --------------
    @staticmethod
    def _fresh_recovery() -> Dict[str, int]:
        """Named rungs of the recovery ladder (stats()["recovery"]): the
        fault-schedule property test demands every injected fault be
        attributable to one of these or to an explicit entry in
        stats()["failed_requests"]."""
        return {
            # sealed-payload integrity failure -> recompute fallback
            "unseal_fallback_swap": 0,
            "unseal_fallback_transfer": 0,
            # device loss: surviving slots spilled to sealed host
            # manifests, then the placement re-solved around the corpse
            "device_loss_spills": 0,
            "device_loss_replans": 0,
            # injected straggler absorbed by a telemetry-driven replan
            "stall_replans": 0,
            # pool-exhaustion storm: slots preempted under storm pressure,
            # seized pages reclaimed (timer expiry or deadlock breaker)
            "storm_preemptions": 0,
            "storm_reclaims": 0,
            # disagg handoff ladder (bumped by DisaggOrchestrator on the
            # decode engine): re-sends after drops, late deliveries, and
            # retry-exhaustion demotions to decode-side re-prefill
            "handoff_retries": 0,
            "handoff_redeliveries": 0,
            "handoff_reprefills": 0,
        }

    def _release_storm(self, reason: str) -> None:
        if not self._storm_pages:
            return
        self._emit("storm_release", {"pages": len(self._storm_pages),
                                     "reason": reason})
        self.pool.release(self._storm_pages)
        self._storm_pages = []
        self._storm_left = 0

    def _fault_storm_tick(self) -> None:
        """Pool-exhaustion storm site, drawn once per step: seize a chunk
        of the free list for a few steps (forcing growth/admission through
        the preemption machinery), then hand it back. The deadlock breaker
        may reclaim the pages early — a storm is never allowed to cost a
        request, only latency."""
        if self.faults is None or self._in_warmup \
                or self.kv_layout != "paged":
            return
        if self._storm_pages:
            self._storm_left -= 1
            if self._storm_left <= 0:
                self._release_storm(reason="timer")
                self.recovery["storm_reclaims"] += 1
            return
        n = self.faults.storm_pages(self.pool.free_pages)
        if n:
            pages = self.pool.alloc(n)
            assert pages is not None, "storm sized from the free list"
            self._storm_pages = pages
            self._storm_left = self.faults.config.storm_steps
            self._emit("fault_storm", {"pages": n,
                                       "steps": self._storm_left})

    def _fault_telemetry_tick(self) -> None:
        """Stall + device-death sites, drawn once per telemetry interval.
        Runs AFTER record_stage_times (whose heartbeat pass marks every
        staged device healthy — injecting earlier would be instantly
        resurrected) and BEFORE maybe_observe, so the replanner's very
        next observation sees the fault exactly as a real heartbeat loss
        or straggler would surface."""
        if self._stall_stage is None:    # one outstanding straggler at a time
            hit = self.faults.pick_stage_stall(self.config.num_stages)
            if hit is not None:
                stage, factor = hit
                self.telemetry.inject(stage, factor)
                self._stall_stage = stage
                self._emit("fault_stall",
                           {"stage": stage, "factor": factor})
        cur = self.replanner.current
        healthy = {d.name for d in self.rm.healthy_domains()}
        used = sorted({s.device for s in cur.placement.stages
                       if s.device in healthy}) if cur is not None else []
        # never kill the last healthy domain: the plane makes recovery
        # expensive, not impossible
        candidates = used if len(healthy) > 1 else []
        victim = self.faults.pick_device_death(candidates)
        if victim is not None:
            self._recover_device_loss(victim)

    def _recover_device_loss(self, victim: str) -> None:
        """Rung 1 of the device-loss ladder: mark the domain dead and
        spill every active slot's KV off the device tier before the
        replanner restages — swap-policy slots seal their private pages
        into host manifests (O(pages) resume, PR 8), recompute-policy
        slots requeue for teacher-forced re-prefill — so no in-flight
        request depends on state the dead device held. Rung 2 fires in
        this same step's maybe_observe: ``replan_on_failure`` excludes
        the corpse and restages through the memoized AOT pairs (zero
        compiles). Rung 3 is the ordinary admission path swapping every
        victim back in bit-identically."""
        self._emit("fault_device_death", {"device": victim})
        self.rm.mark_unhealthy(victim)
        self._death_pending = True
        if self.kv_layout == "paged" \
                and self.config.page_policy == "demand":
            for slot, req in sorted(self.scheduler.active(),
                                    key=lambda t: t[1].rid, reverse=True):
                if self.scheduler.slots[slot] is not req:
                    continue        # already evicted by a cascade
                self._preempt(slot, req)
                self.recovery["device_loss_spills"] += 1

    def _stall_recoverable(self) -> bool:
        """Satellite bugfix: a head-of-line stall is *recoverable* while
        some pending mechanism can still free the blocking pages or
        re-deliver the blocked request — parked swap/transfer manifests
        (the deadlock breaker can demote or drop them), an active
        injected storm (its pages come back), or in-flight handoff
        retries an orchestrator still holds. Only with none of those is
        the engine permanently stalled."""
        if self.pending_external > 0 or self._storm_pages:
            return True
        return (self.kv_layout == "paged" and self.pool is not None
                and bool(self.pool.swap_manifest
                         or self.pool.transfer_manifest))

    def _alloc_or_preempt(self, requester: Request) -> Optional[int]:
        """One page for ``requester``, preempting the lowest-priority
        (= youngest, max rid) active slot whenever the pool is dry and the
        COW index has nothing evictable. Terminates: every iteration either
        yields a page or removes one active slot, and once ``requester`` is
        the sole survivor the submit-time progress guarantee says a page
        exists. Returns None iff ``requester`` itself was preempted — the
        caller must then skip it this step (it is requeued, not lost)."""
        while True:
            pg = self.pool.alloc_one()
            if pg is not None:
                return pg
            active = self.scheduler.active()
            assert active, "pool dry with no active slots"
            victim_slot, victim = max(active, key=lambda t: t[1].rid)
            self._preempt(victim_slot, victim)
            if victim is requester:
                return None

    def _grow_active(self) -> None:
        """Before each decode step, make every active slot's next write
        position backed by a private page: grow the block table when the
        position enters a new page, and fork (copy) the target page first
        when it is shared (refcount > 1 — another slot or the COW index
        holds it). Runs oldest-request-first so preemption priority
        (youngest dies first) is respected when the pool is tight.
        PREFILL (mid-chunk) slots are skipped: they write via the chunk
        scatter path, which acquires its own pages."""
        if self.kv_layout != "paged" or self.config.page_policy != "demand":
            return
        Pg = self.config.page_size
        for slot, req in sorted(self.scheduler.decoding(),
                                key=lambda t: t[1].rid):
            if self.scheduler.slots[slot] is not req:
                continue                 # preempted earlier in this pass
            pages = self.slot_pages[slot]
            pi = self.slot_len[slot] // Pg
            if pi >= len(pages):
                pg = self._alloc_or_preempt(req)
                if pg is None:
                    continue
                pages.append(pg)
                bt_idx = len(pages) - 1
                assert bt_idx < self.pages_per_slot
                self.backend.set_table_entry(slot, bt_idx, pg)
            elif self.pool.refcount[pages[pi]] > 1:
                pg = self._alloc_or_preempt(req)
                if pg is None:
                    continue
                self.backend.copy_page(pg, pages[pi])
                self.pool.decref(pages[pi])
                old = pages[pi]
                pages[pi] = pg
                self.pool.forks += 1
                self.backend.set_table_entry(slot, pi, pg)
                self._emit("fork", {"rid": req.rid, "slot": slot,
                                    "from": old, "to": pg})

    def _admit(self) -> None:
        while True:
            nxt = self.scheduler.peek()
            if nxt is None:
                return
            if not self._fits(nxt):
                if self._maybe_break_swap_deadlock(nxt):
                    continue
                if self._blocked_rid != nxt.rid:
                    self._blocked_rid = nxt.rid
                    kind = ("pages" if self.kv_layout == "paged"
                            else "timeline")
                    self._emit("backpressure",
                               {"rid": nxt.rid, "waiting_on": kind})
                return
            self._blocked_rid = None
            if self._prefill_at_packed is not None and self._packable(nxt):
                with self.telemetry.span("serve.admit", rid=nxt.rid):
                    self._admit_packed()
                continue
            hit = self.scheduler.admit_next(step=self.steps)
            assert hit is not None
            with self.telemetry.span("serve.admit", rid=hit[1].rid):
                self._prefill_slot(*hit)

    def _packable(self, req: Request) -> bool:
        """Can ``req`` join a packed prefill group? Plain one-shot paged
        admissions only — swap resumes and handoff ingests restore KV
        instead of prefilling, and chunked prompts stream over steps."""
        if self.pool.has_swap(req.rid) or self.pool.has_transfer(req.rid):
            return False
        C = self.config.prefill_chunk
        return not (C > 0 and len(self._prompt_tokens(req)) > C)

    def _admit_packed(self) -> None:
        """Greedily admit up to ``prefill_pack`` packable queued requests,
        acquiring each one's pages as it joins (so the next mate's _fits
        gate sees the pool state its own allocation will find), then
        prefill the whole group in ONE shared bucketed call."""
        t0 = time.perf_counter()
        group: List[Tuple[int, Request, List[int], List[int], List[bool]]] \
            = []
        while len(group) < self.config.prefill_pack:
            nxt = self.scheduler.peek()
            if nxt is None or not self._packable(nxt) \
                    or not self._fits(nxt):
                break
            slot, req = self.scheduler.admit_next(step=self.steps)
            tokens = self._prompt_tokens(req)
            pages, shared = self._acquire_pages(req)
            self.slot_pages[slot] = pages
            self.slot_len[slot] = len(tokens)
            group.append((slot, req, tokens, pages, shared))
        assert group, "caller verified the head fits and is packable"
        self._prefill_packed(group, t0)

    def _prefill_packed(self, group, t0: float) -> None:
        """One shared bucketed prefill over the group: tokens padded to
        [K, S_pad] (K = prefill_pack always — dummy all-pad rows keep the
        compiled shape inventory at one entry per bucket), logits read
        per-row at each prompt's own last position, KV scattered per slot
        with the same drop-sentinel discipline as one-shot admission. Each
        row's stream is bit-identical to its solo admission: rows are
        batch-independent and padding positions never reach the extracted
        logit or the pools."""
        cfg = self.config
        K = cfg.prefill_pack
        seg = self.api.model.segments[0].name
        Pg, N = cfg.page_size, self.pool.num_pages
        S_pad = self._bucket(max(len(t) for _, _, t, _, _ in group))
        toks = np.zeros((K, S_pad), np.int32)
        plens = np.ones(K, np.int32)    # dummy rows extract position 0
        for b, (_, _, tokens, _, _) in enumerate(group):
            toks[b, :len(tokens)] = tokens
            plens[b] = len(tokens)
        logits, caches = self._prefill_at_packed(
            self.params, {"tokens": jnp.asarray(toks),
                          "prompt_lens": jnp.asarray(plens)})
        kk_all, vv_all = caches[seg]    # [L, K, KVH, S_pad, D]
        self.prefill_calls += 1
        self.packed_prefills += 1
        for b, (slot, req, tokens, pages, shared) in enumerate(group):
            P = len(tokens)
            bt_row = np.zeros(self.pages_per_slot, np.int32)
            bt_row[:len(pages)] = pages
            idx = np.arange(S_pad)
            page_of = np.minimum(idx, P - 1) // Pg
            shared_of = np.asarray(shared, bool)[page_of]
            skip = (idx >= P) | shared_of
            pages_vec = np.where(skip, N,
                                 np.asarray(pages, np.int32)[page_of])
            offs_vec = np.where(idx < P, idx % Pg, 0).astype(np.int32)
            self.backend.insert_slot(
                slot, (kk_all[:, b], vv_all[:, b]),
                jnp.asarray(pages_vec.astype(np.int32)),
                jnp.asarray(offs_vec), jnp.asarray(bt_row), P)
            with self.telemetry.span("serve.device_wait"):
                first = self.sampler.sample_one(logits[b:b + 1], req.rid,
                                                len(req.generated))
            self.pending[slot] = first
            ms = (time.perf_counter() - t0) * 1e3
            self.admission_ms.append(ms)
            self.admissions += 1
            self.packed_admissions += 1
            detail = {"rid": req.rid, "slot": slot, "pages": len(pages),
                      "shared": int(sum(shared)), "packed": len(group),
                      "ms": ms}
            if req.generated:
                detail["resumed_at"] = len(req.generated)
            self._emit("admit", detail)
            fin = self.scheduler.on_token(slot, first, step=self.steps)
            if fin is not None:
                self._on_finish(fin)

    # -- one decode step ---------------------------------------------------
    def step(self) -> List[EngineEvent]:
        with self.telemetry.span("serve.step"):
            return self._step()

    def _step(self) -> List[EngineEvent]:
        self._step_events = []
        self._fault_storm_tick()
        self._admit()
        # chunked prefill: at most ONE prompt chunk per engine step,
        # interleaved with the decode tick below so batch-mates keep
        # emitting tokens while a long prompt fills in
        self._advance_chunks()
        # demand paging: back every decoding slot's next write position
        # with a private page (grow / fork / preempt) BEFORE the step,
        # so the jitted decode never scatters into a shared page
        with self.telemetry.span("serve.grow"):
            self._grow_active()
        active = self.scheduler.decoding()
        if not active:
            if self.chunking:
                # chunk-only step: prefill progressed, nothing decodes
                # yet — the engine clock still ticks (wait accounting)
                # but the shared timeline must NOT advance
                self.steps += 1
                self.stalled = False
                return self._step_events
            # head-of-line blocked with nothing running: no completion
            # can ever free the resource it waits on -> permanently
            # stalled (callers stop driving; requests stay queued) —
            # UNLESS a pending mechanism can still unblock the head
            # (_stall_recoverable: manifest pins the deadlock breaker
            # can demote/drop, an active storm, in-flight handoff
            # retries), so the stall is not permanent yet
            recoverable = self._stall_recoverable()
            self.stalled = bool(self.scheduler.queue) and not recoverable
            self.stall_reason = None if not self.scheduler.queue else \
                ("recoverable" if recoverable else "permanent")
            return self._step_events
        self.stalled = False
        self.stall_reason = None
        self.peak_running = max(self.peak_running, len(active))
        if self.kv_layout == "timeline":
            # unreachable: _fits() only admits requests whose worst-case
            # generation ends inside the horizon
            assert self.global_len < self.config.max_seq - 1, \
                "timeline horizon violated despite admission gating"

        if self.kv_layout == "paged":
            # pages the attention kernel streams (this step's token
            # included) against the pages the block tables span
            Pg = self.config.page_size
            live_pages = sum(-(-(self.slot_len[slot] + 1) // Pg)
                             for slot, _ in active)
        with self.telemetry.span("serve.dispatch"):
            tokens = jnp.asarray(self.pending)[:, None]
            t0 = time.perf_counter()
            logits = self.backend.step(tokens, self._key + self.steps)
        with self.telemetry.span("serve.device_wait"):
            logits = jax.block_until_ready(logits)
        wall = time.perf_counter() - t0
        self.steps += 1
        self.global_len += 1

        # per-slot PRNG keys thread (rid, within-request position), so a
        # sampled stream is slot/admission/batch-mate independent
        with self.telemetry.span("serve.sample"):
            rids = np.zeros(self.config.num_slots, np.int64)
            idxs = np.zeros(self.config.num_slots, np.int64)
            for slot, req in active:
                rids[slot] = req.rid
                idxs[slot] = len(req.generated)
            toks = self.sampler.sample(logits, rids, idxs)
        with self.telemetry.span("serve.emit"):
            for slot, req in active:
                self.pending[slot] = toks[slot]
                if self.kv_layout == "paged":
                    self.slot_len[slot] += 1   # this step's KV write landed
                    self._maybe_register_decode_page(slot, req)
                fin = self.scheduler.on_token(slot, int(toks[slot]),
                                              step=self.steps)
                if fin is not None:
                    self._on_finish(fin)

        # telemetry tick → maybe re-plan → maybe swap. Warmup traffic is
        # synthetic: keep it out of the measured wall clock and the
        # replanner's EMAs so the first real serve starts clean.
        with self.telemetry.span("serve.telemetry"):
            if not self._in_warmup:
                self.telemetry.record_step(wall)
                if self.kv_layout == "paged":
                    self.telemetry.record_decode_attention(
                        live_pages,
                        self.config.num_slots * self.pages_per_slot)
                if self.steps % self.telemetry.interval == 0:
                    times = self.backend.stage_times()
                    if times is None:
                        shares = self.telemetry.predicted_shares()
                        times = [wall * s for s in shares]
                    if times:
                        self.telemetry.record_stage_times(times)
                    if self.faults is not None:
                        self._fault_telemetry_tick()
                new_spec = self.telemetry.maybe_observe(self.steps)
                if new_spec is not None:
                    if self._death_pending:
                        self.recovery["device_loss_replans"] += 1
                        self._death_pending = False
                    if self._stall_stage is not None:
                        # the replan absorbed the injected straggler;
                        # clear the factor so the new placement measures
                        # clean
                        self.telemetry.inject(self._stall_stage, 1.0)
                        self._stall_stage = None
                        self.recovery["stall_replans"] += 1
                    self._emit("replan",
                               {"blocks": new_spec.stage_sizes(),
                                "placement": new_spec.describe()})
                    if self.config.allow_swap:
                        self.try_swap(new_spec.stage_sizes())
                    # adopt the spec only once the executing layout matches
                    # it (swap applied, or sizes unchanged and only devices
                    # moved); a skipped swap keeps self.spec on what the
                    # backend runs
                    if new_spec.stage_sizes() == self.stage_blocks:
                        self.spec = new_spec
        return self._step_events

    def _maybe_register_decode_page(self, slot: int, req: Request) -> None:
        """Decode-time COW registration (``decode_cow``): when this step's
        KV write filled a page to capacity, freeze it into the prefix index
        under its content key — an identical continuation (a fan-out
        resubmission whose prompt extends through this page) then adopts it
        instead of re-prefilling, counted by the existing ``cow_hits``
        stat. Only full pages register (the owner never writes a full page
        again — growth moved on — so indexed content stays immutable), and
        only private un-indexed pages (a shared or already-frozen page is
        either someone else's or already registered)."""
        cfg = self.config
        if not (cfg.decode_cow and cfg.prefix_sharing
                and cfg.page_policy == "demand"):
            return
        Pg = cfg.page_size
        sl = self.slot_len[slot]
        if sl % Pg:
            return                      # page not full yet
        pi = sl // Pg - 1
        pages = self.slot_pages[slot]
        if pi >= len(pages):
            return
        pg = pages[pi]
        if self.pool.refcount[pg] != 1 or pg in self.pool._page_key:
            return
        # content key = every token whose KV the page and its predecessors
        # hold: positions [0, sl) carry prompt + generated[:g] (the token
        # sampled THIS step is pending, not yet written — exactly sl tokens)
        key = tuple(req.prompt) + tuple(int(t) for t in req.generated)
        assert len(key) == sl, (len(key), sl)
        if key in self.pool.prefix_index:
            return                      # another slot froze this content
        self.pool.register_prefix(key, pg)

    # -- live boundary swap ------------------------------------------------
    def try_swap(self, blocks: Sequence[int]) -> bool:
        blocks = tuple(blocks)
        if blocks == self.stage_blocks:
            return False
        if len(blocks) != self.config.num_stages or \
                sum(blocks) != self.api.model.segments[0].n:
            self._emit("swap_skipped", {"blocks": blocks})
            return False
        migrated = self.backend.swap(blocks)
        self._emit("swap", {"from": self.stage_blocks, "to": blocks,
                            "migrated": migrated and
                            self.backend.migrates_cache})
        self.stage_blocks = blocks
        self.swaps += 1
        return True

    # -- AOT warmup: compile the full serving shape inventory --------------
    def warmup(self) -> float:
        """Compile every shape the steady-state serving loop can dispatch,
        then freeze the AOT registry: any XLA compilation after this point
        is a bug, counted by ``stats()["post_warmup_compiles"]`` (asserted
        zero in tests/CI) and named in ``stats()["compile_stalls"]``.

        Three passes (DESIGN.md §AOT warmup & chunked prefill):

        1. *traffic* — synthetic requests through the REAL submit/step path
           (one per prefill bucket, a COW twin pair, a chunked long prompt),
           so host-side eager ops and the backend's sharding evolution
           (unsharded first insert → pod-sharded steady state) are exercised
           exactly as serving will;
        2. *direct* — every AOT entry point traffic can't reach is called
           state-neutrally (all prefill buckets, page maintenance ops, the
           chunk kernel, a null decode tick);
        3. *layouts* — for swappable pipelined backends, tour up to
           ``warmup_layouts`` alternative stage layouts so a live re-plan
           swaps onto prebuilt decoders with seeded dispatch caches.

        The engine state is then reset to factory-fresh (same rids, clocks
        and telemetry a cold engine starts with — warmed and cold engines
        produce token-identical streams) and pass 2 re-runs on the fresh
        unsharded state. Idempotent in effect; meaningful only on a fresh
        engine, asserted below."""
        assert self.steps == 0 and not self.scheduler.has_work() \
            and not self.chunking, "warmup() must run on a fresh engine"
        t0 = time.perf_counter()
        MONITOR.install()
        self._in_warmup = True
        try:
            self._warm_traffic()
            self._warm_direct()
            if self.backend_kind == "pipelined" and \
                    self.config.allow_swap:
                self._warm_layouts()
            self._reset_state()
            if self.kv_layout == "paged":
                # the reset re-created unsharded device state: re-seed the
                # (shape, sharding)-keyed dispatch caches for the first
                # real admissions (state-neutral for paged layouts)
                self._warm_direct()
        finally:
            self._in_warmup = False
        self.aot.freeze()
        self.warmed = True
        self.warmup_s = time.perf_counter() - t0
        return self.warmup_s

    def _bucket_inventory(self) -> List[int]:
        """Every prefill bucket ``_bucket()`` can emit: pow2 sizes up to
        prompt_capacity, plus the preemption-extended sizes up to
        request_capacity under the paged layout."""
        if self.kv_layout != "paged":
            return []
        cap = self.request_capacity
        return sorted({self._bucket(n) for n in range(1, cap + 1)})

    def _warm_traffic(self) -> None:
        """Synthetic requests through the real serve path. Deterministic
        token content (keyed off sample_seed) so COW twin adoption and the
        fork-on-divergence growth path reproduce across runs."""
        cfg = self.config
        V = self.api.cfg.vocab_size

        def toks(n: int, salt: int) -> List[int]:
            return [int((cfg.sample_seed * 7919 + salt * 31 + j) % V)
                    for j in range(n)]

        prompts: List[List[int]] = []
        if self.kv_layout == "paged":
            for i, b in enumerate(x for x in self._bucket_inventory()
                                  if x <= cfg.prompt_capacity):
                prompts.append(toks(b, i))
            # identical twins spanning a partial tail page: COW adoption at
            # the second admission, then a fork when decode growth first
            # writes into the shared tail
            twin = toks(min(cfg.page_size + 2, cfg.prompt_capacity), 101)
            prompts += [twin, list(twin)]
            if cfg.prefill_chunk and cfg.prompt_capacity > cfg.prefill_chunk:
                prompts.append(toks(cfg.prompt_capacity, 202))
        else:
            # timeline shapes are length-independent ([1,1] token prefill,
            # fixed-horizon cache): one short request covers them
            prompts.append(toks(2, 7))
        for p in prompts:
            if self.kv_layout == "paged":
                mn = max(1, min(2, self.request_capacity - len(p)))
                if self.pool.pages_needed(len(p) + mn) + 1 > \
                        self.pool.num_pages - 1:
                    continue            # unadmittable in real serve too
            else:
                mn = max(1, min(2, cfg.max_seq - self.global_len))
            self.submit(p, mn)
        guard = 0
        while self.scheduler.has_work():
            self.step()
            assert not self.stalled, "warmup traffic stalled"
            guard += 1
            assert guard < 10_000, "warmup traffic failed to drain"

    def _warm_direct(self) -> None:
        """State-neutral direct calls into every AOT entry point: prefill
        at every bucket + an insert whose page vector is all drop-sentinel
        (nothing lands, null page stays zero), the page maintenance ops on
        the null page / slot 0's already-clear row, one chunk against the
        sentinel, a decode tick on idle slots, and the stage probes."""
        if self.kv_layout != "paged":
            if self.steps == 0:
                # traffic had no room for a decode tick: take one here
                # (pre-reset only — the timeline cache advances)
                self._warm_step_neutral()
            self.backend.stage_times()
            return
        seg = self.api.model.segments[0].name
        N, MP = self.pool.num_pages, self.pages_per_slot
        zeros_row = jnp.asarray(np.zeros(MP, np.int32))
        for b in self._bucket_inventory():
            if self.config.batched_prefill:
                _, caches = self._prefill_at(
                    self.params, {"tokens": jnp.asarray(
                        np.zeros((1, b), np.int32)),
                        "prompt_len": jnp.int32(b)})
                kk, vv = caches[seg]
                kv = (kk[:, 0], vv[:, 0])
            else:
                cache = self.api.init_cache(1, b)
                _, cache = self._prefill(
                    self.params, cache,
                    {"tokens": jnp.asarray(np.zeros((1, 1), np.int32))})
                kk, vv = cache[seg]
                kv = (kk[:, 0, :, :b], vv[:, 0, :, :b])
            self.backend.insert_slot(
                0, kv, jnp.asarray(np.full(b, N, np.int32)),
                jnp.asarray(np.zeros(b, np.int32)), zeros_row, 0)
        if self._prefill_at_packed is not None:
            # packed prefill compiles one shape per bucket at the fixed
            # group width K (dummy rows pad short groups); the per-row
            # inserts reuse the single-path shapes warmed just above
            K = self.config.prefill_pack
            for b in self._bucket_inventory():
                self._prefill_at_packed(
                    self.params,
                    {"tokens": jnp.asarray(np.zeros((K, b), np.int32)),
                     "prompt_lens": jnp.asarray(np.ones(K, np.int32))})
        self.backend.copy_page(0, 0)
        self.backend.set_table_entry(0, 0, 0)
        self.backend.commit_slot(0, zeros_row, 0)
        self.backend.clear_slot(0)
        C = self.config.prefill_chunk
        if C > 0:
            self.backend.prefill_chunk(
                jnp.asarray(np.zeros((1, C), np.int32)), 0, C,
                jnp.asarray(np.zeros((1, MP), np.int32)),
                jnp.asarray(np.full(C, N, np.int32)),
                jnp.asarray(np.zeros(C, np.int32)))
        self._warm_swap_io()
        self._warm_step_neutral()
        self.backend.stage_times()

    def _warm_swap_io(self) -> None:
        """State-neutral warm of the two-tier swap transfer path: gather
        the null page for every row (seal + device→host fetch, exactly the
        swap-out shapes) and scatter the payload back with every row on
        the drop sentinel (unseal + scatter executable, nothing lands).
        Runs under the planned layout here and under each toured layout in
        ``_warm_layouts`` — swap traffic then causes zero post-warmup
        compiles regardless of which layout is live. Disaggregated engines
        warm it whatever their preempt policy: handoff export/ingest reuse
        these exact executables (the counter is a traced argument)."""
        if self.kv_layout != "paged" or (
                self.preempt_policy != "swap"
                and not self.config.disagg_role):
            return
        MP, N = self.pages_per_slot, self.pool.num_pages
        ctr = jnp.uint32(0)
        ck, cv = self.backend.gather_pages(
            jnp.asarray(np.zeros(MP, np.int32)), self._key, ctr)
        # round-trip through host numpy: real swap-in feeds host-resident
        # payload buffers, and the AOT signature must match it exactly
        ck, cv = np.asarray(ck), np.asarray(cv)
        self.backend.scatter_pages(
            jnp.asarray(ck), jnp.asarray(cv),
            jnp.asarray(np.full(MP, N, np.int32)), self._key, ctr)

    def _warm_step_neutral(self) -> None:
        """One decode tick on all-idle slots: every seq_len is 0, so paged
        writes land on the null page's drop path and state is unchanged."""
        toks = jnp.asarray(np.zeros((self.config.num_slots, 1), np.int32))
        jax.block_until_ready(self.backend.step(toks,
                                                self._key + self.steps))

    def _swap_targets(self) -> List[Tuple[int, ...]]:
        """Stage layouts to prewarm: ALL compositions of n blocks into
        num_stages stages when that inventory is small enough, else the
        adjacent single-block shifts of the planned layout (the replanner's
        most likely moves), capped at ``warmup_layouts``."""
        n = self.api.model.segments[0].n
        S = self.config.num_stages
        planned = self.stage_blocks
        if S <= 1 or n < S:
            return []
        if math.comb(n - 1, S - 1) - 1 <= self.config.warmup_layouts:
            out = []
            for cuts in itertools.combinations(range(1, n), S - 1):
                bounds = (0,) + cuts + (n,)
                blocks = tuple(b - a for a, b in zip(bounds, bounds[1:]))
                if blocks != planned:
                    out.append(blocks)
            return out
        seen, out = {planned}, []
        for i in range(S - 1):
            for d in (1, -1):
                blocks = list(planned)
                blocks[i] -= d
                blocks[i + 1] += d
                t = tuple(blocks)
                if min(blocks) >= 1 and t not in seen:
                    seen.add(t)
                    out.append(t)
        return out[:self.config.warmup_layouts]

    def _warm_layouts(self) -> None:
        """Tour alternative stage layouts: each try_swap builds (and caches)
        the target's decoder + staged params, runs its probes and two
        neutral decode ticks, then swaps home — so a post-freeze re-plan
        onto any toured layout (and the swap home) hits only prebuilt
        executables. Swaps between two non-planned layouts the replanner
        chains through hit the backends' lazy restage memo instead: the
        first occurrence of a (from, to) pair AOT-warms its composed
        gather off the stall ledger (one-off wall cost, no recorded
        stall), and every repeat dispatches from the memo."""
        planned = self.stage_blocks
        for target in self._swap_targets():
            if not self.try_swap(target):
                continue
            self.backend.stage_times()
            for _ in range(2):
                self._warm_step_neutral()
            if self.kv_layout == "paged":
                self._warm_swap_io()    # per-layout swap transfer fns
            self.try_swap(planned)
        assert self.stage_blocks == planned

    def _reset_state(self) -> None:
        """Factory-reset every piece of serving state warmup traffic
        touched — scheduler (rids restart at 0, so sampler keystreams match
        a cold engine), page pool, device caches, clocks, counters, events,
        measured telemetry — leaving only the compiled inventory behind."""
        cfg = self.config
        self.scheduler = SlotScheduler(cfg.num_slots,
                                       finished_cap=cfg.finished_cap)
        if self.kv_layout == "paged":
            # a fresh pool also clears the swap manifests (warmup traffic
            # may have swapped); their host payloads die with them
            self.pool = PagePool(self.pool.num_pages, cfg.page_size)
            self.slot_pages.clear()
            self.slot_len.clear()
        self._swap_seq = 0
        self.swap_fallbacks = 0
        self._transfer_seq = 0
        self.transfers_out = 0
        self.packed_admissions = 0
        self.packed_prefills = 0
        self.chunking.clear()
        self.pending[:] = 0
        self.steps = 0
        self.global_len = cfg.prompt_capacity
        self.swaps = 0
        self.preemptions = 0
        self.peak_running = 0
        self.stalled = False
        self._blocked_rid = None
        self.admission_ms.clear()
        self.admissions = 0
        self.prefill_calls = 0
        self.chunked_admissions = 0
        self.chunk_steps = 0
        self.events.clear()
        self._step_events = []
        # fault plane: re-seed so the post-warmup serve replays the exact
        # schedule a cold engine would see (storm pages died with the pool)
        if self.faults is not None:
            self.faults.reset()
        self.recovery = self._fresh_recovery()
        self.failed.clear()
        self.pending_external = 0
        self.stall_reason = None
        self._storm_pages = []
        self._storm_left = 0
        self._death_pending = False
        self._stall_stage = None
        self.telemetry.reset_measurements()
        self.backend.reset_state()

    # -- drive to completion ----------------------------------------------
    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        n = 0
        while self.scheduler.has_work():
            if max_steps is not None and n >= max_steps:
                break
            self.step()
            if self.stalled:
                # permanent back-pressure (nothing active, head blocked):
                # return instead of spinning; queued requests stay queued
                break
            n += 1
        return list(self.scheduler.finished)

    def run_trace(self, arrivals: Sequence[Tuple[int, Sequence[int], int,
                                                 Optional[int]]],
                  max_steps: Optional[int] = None) -> List[Request]:
        """Replay a timed arrival trace (``benchmarks/load_trace.py``):
        each ``(step, prompt, max_new, eos_id)`` is submitted once the
        engine clock reaches its arrival step; idle gaps fast-forward the
        clock to the next arrival. Returns every submitted Request (the
        trace is fully deterministic under a fixed seed)."""
        arrivals = sorted(arrivals, key=lambda a: a[0])
        reqs: List[Request] = []
        k, n = 0, 0
        while k < len(arrivals) or self.scheduler.has_work():
            if max_steps is not None and n >= max_steps:
                break
            while k < len(arrivals) and arrivals[k][0] <= self.steps:
                _, prompt, max_new, eos = arrivals[k]
                reqs.append(self.submit(list(prompt), max_new, eos_id=eos))
                k += 1
            if not self.scheduler.has_work():
                # idle until the next arrival: jump the clock to it
                self.steps = max(self.steps, arrivals[k][0])
                continue
            self.step()
            if self.stalled:
                break
            n += 1
        return reqs

    # -- test hook: pool/refcount audit ------------------------------------
    def check_page_invariants(self) -> None:
        """Assert the PagePool's refcount/partition invariants against the
        engine's live block tables (property-test hook; no device work)."""
        if self.kv_layout == "paged":
            tables: Dict[Any, Any] = dict(self.slot_pages)
            if self._storm_pages:
                # storm-seized pages are live references held by the fault
                # plane, not a leak — audit them like a block table
                tables["storm"] = self._storm_pages
            self.pool.check_invariants(tables)

    def stats(self) -> Dict[str, Any]:
        out = dict(self.scheduler.stats())
        wall = self.telemetry.wall_s
        step_s = self.telemetry.span_s["serve.step"]
        out.update({
            "steps": self.steps,
            "swaps": self.swaps,
            "replans": self.replanner.replans,
            "failure_replans": self.replanner.failure_replans,
            "excluded_devices": list(self.replanner.excluded_devices),
            "backend": self.backend_kind,
            "kv_layout": self.kv_layout,
            "stage_blocks": self.stage_blocks,
            "placement": self.spec.describe(),
            "decode_wall_s": wall,
            "tok_per_s": (out["tokens_out"] / step_s) if step_s > 0
            else 0.0,
            "prefill_calls": self.prefill_calls,
            "admissions": self.admissions,
            "warmed": self.warmed,
            "warmup_s": self.warmup_s,
            # None until warmup() froze the registry (or the compile monitor
            # could not install); 0 is the steady-state guarantee
            "post_warmup_compiles": self.aot.post_freeze_compiles,
            "compile_stalls": [s.describe()
                               for s in self.aot.post_freeze_stalls],
            # chaos fault plane: the recovery ladder's named rungs, the
            # per-request failure ledger, and the stall classification
            # (satellite: a retry-in-progress is NOT a permanent stall)
            "stalled": self.stalled,
            "stall_reason": self.stall_reason,
            "pending_external": self.pending_external,
            "recovery": dict(self.recovery),
            "failed_requests": dict(self.failed),
        })
        if self.faults is not None:
            out["faults"] = self.faults.snapshot()
            # injections whose recovery rung has not completed yet (a
            # drained engine may end with a stall injected after the last
            # replan tick, a storm mid-lifetime, …): the accounting
            # property charges each injected fault to a recovery counter
            # OR one of these in-progress markers — nothing vanishes
            out["faults_pending"] = {
                "death": self._death_pending,
                "stall": self._stall_stage is not None,
                "storm": bool(self._storm_pages),
            }
        if self.admission_ms:
            arr = np.asarray(self.admission_ms)
            out["admission_p50_ms"] = float(np.percentile(arr, 50))
            out["admission_p99_ms"] = float(np.percentile(arr, 99))
        if self.kv_layout == "paged":
            out["page_size"] = self.config.page_size
            out["num_pages"] = self.pool.num_pages
            out["free_pages"] = self.pool.free_pages
            out["peak_pages_in_use"] = self.pool.peak_in_use
            out["peak_demand_pages"] = self.pool.peak_demand
            out["page_policy"] = self.config.page_policy
            out["preemptions"] = self.preemptions
            out["preempt_policy"] = self.preempt_policy
            out.update(self.pool.stats())   # swapped_pages/swap_outs/ins
            #                                 + pending_transfers/
            #                                 transfers_in/demotions
            out["swap_fallbacks"] = self.swap_fallbacks
            out["disagg_role"] = self.config.disagg_role
            out["transfers_out"] = self.transfers_out
            out["prefill_pack"] = self.config.prefill_pack
            out["packed_admissions"] = self.packed_admissions
            out["packed_prefills"] = self.packed_prefills
            out["decode_cow"] = self.config.decode_cow
            out["cow_hits"] = self.pool.cow_hits
            out["forks"] = self.pool.forks
            out["evictions"] = self.pool.evictions
            out["peak_running_slots"] = self.peak_running
            out["prefill_chunk"] = self.config.prefill_chunk
            out["chunked_admissions"] = self.chunked_admissions
            out["prefill_chunks"] = self.chunk_steps
            out["decode_attn_path"] = self.backend.attn_path
            out["decode_attn_live_pages"] = self.telemetry.attn_live_pages
            out["decode_attn_table_pages"] = self.telemetry.attn_table_pages
        return out
