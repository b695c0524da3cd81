"""Disaggregated prefill/decode serving across trust domains.

Prefill is compute-bound (one big batched matmul pass over the prompt);
decode is memory-bound (streaming the KV cache past the weights one token
at a time). Serdab's partitioning argument — confine the privacy-critical
state to the enclave, run bulk compute outside (PAPERS.md: YerbaBuena's
ternary splits, Privado) — applies directly: run *prefill* on a fast,
possibly untrusted device and *decode* inside the trusted domain, shipping
the prompt's KV across the boundary sealed under the PR 8 bit-cipher.

Three pieces (DESIGN.md §Disaggregated prefill/decode):

* ``PrefillEngine`` — wraps a ``ServingEngine`` in the prefill role: it
  admits requests (bucketed, packed, or chunked prefill — never a decode
  tick), samples each request's FIRST token, then immediately seals every
  KV page of the finished slot (``export_transfer``: one warmed
  ``gather_pages`` keyed in the dedicated transfer counter space) and
  vacates the slot. The output is a stream of ``(Request,
  TransferManifest)`` handoffs.
* ``DisaggOrchestrator`` — owns the global rid counter (so the sampler's
  ``(rid, index)`` keystreams match a monolithic engine's submission
  order), routes submissions to the prefill engine, applies back-pressure
  when the decode side has no admission room, ships manifests into the
  decode engine (``ingest_transfer`` resolves rows against the decode
  pool's COW index), and ticks decode. With no prefill peer it degrades
  gracefully to driving the decode engine monolithically.
* ``plan_disagg_roles`` — scores (prefill domain, decode domain) pairs
  over the trust-domain ``ResourceManager``: roofline prefill/decode
  times, seal+link cost of the KV handoff, and the ``cut_exposure``
  leakage price of letting an untrusted device see the prompt. Decode must
  be trusted (the transcript and its KV never leave the enclave);
  untrusted prefill is allowed and — on the default two-pod topology —
  wins, because the full-rate pod amortizes the handoff.

Streams are bit-identical to the monolithic engine (property-tested in
tests/test_disagg.py, asserted in CI via ``serve --verify-disagg``): both
engines share params and sampler config, the first token is sampled on the
prefill side with the same ``(rid, index)`` key the monolithic engine
would use, and ``_transfer_in`` resumes decode exactly like a swap-in —
the first token was never written to KV, so it is the next decode input.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.cost_model import seal_time, transmit_time
from repro.core.planner import profiles_from_arch
from repro.core.privacy import cut_exposure
from repro.enclave.domain import ResourceManager
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.scheduler import Request, TransferManifest


# ---------------------------------------------------------------------------
# Role planning over trust domains
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RoleCandidate:
    """One scored (prefill, decode) domain assignment."""

    prefill_domain: str
    decode_domain: str
    prefill_s: float            # roofline prompt pass on the prefill device
    seal_s: float               # seal (src) + unseal (dst) of the KV pages
    link_s: float               # manifest transfer over the connecting link
    decode_s: float             # max_new roofline decode steps
    interference_s: float       # colocated only: peer prefills stalling decode
    leakage: float              # cut_exposure price of untrusted prefill

    @property
    def latency_s(self) -> float:
        return (self.prefill_s + self.seal_s + self.link_s + self.decode_s
                + self.interference_s)


@dataclasses.dataclass(frozen=True)
class RolePlan:
    prefill_domain: str
    decode_domain: str
    latency_s: float
    leakage: float
    handoff_bytes: float
    candidates: Tuple[RoleCandidate, ...]   # every legal pair, best first

    def describe(self) -> str:
        return (f"prefill@{self.prefill_domain} -> decode@"
                f"{self.decode_domain} ({self.latency_s * 1e3:.2f} ms, "
                f"leakage {self.leakage:.3g})")


def plan_disagg_roles(rm: ResourceManager, model_cfg, *,
                      prompt_len: int = 256, max_new: int = 64,
                      page_size: int = 16, concurrency: int = 16,
                      bytes_per_el: int = 2) -> RolePlan:
    """Pick which trust domain runs each role.

    Trust policy: the decode domain MUST be trusted — generated tokens and
    their KV never leave the enclave. The prefill domain may be untrusted;
    that candidate carries a ``cut_exposure`` leakage price (the prompt is
    processed in the clear there, similarity 1.0 by definition), mirroring
    how ``PlacementSpec.cut_costs`` prices boundary cuts: leakage is
    *recorded* on the plan, latency decides — same contract as the layer
    planner (ROADMAP: leakage-budgeted solving is a separate open item).

    Costs are the same roofline terms the layer cost model uses: prefill =
    whole-prompt flops on the prefill device + per-layer dispatch; handoff
    = seal at the source + transmit page-granular KV over the connecting
    link + unseal at the destination; decode = ``max_new`` memory-bound
    steps (weights + the growing KV stream) on the decode device.

    The **colocated** candidate (prefill domain == decode domain, i.e.
    monolithic serving) skips the handoff entirely but pays *interference*:
    under continuous batching at ``concurrency`` resident requests, every
    peer prompt admitted during this request's decode stalls the shared
    device for one full prefill pass — roughly ``concurrency`` stalls over
    the request's lifetime. Disaggregated decode never runs prefill, so it
    pays none. This is the throughput case for disaggregation: at
    ``concurrency=1`` colocated wins (no handoff, nothing to stall); at
    serving concurrency the interference dwarfs the sealed handoff and the
    untrusted full-rate pod takes prefill.
    """
    prof_prefill = profiles_from_arch(model_cfg, seq_len=prompt_len,
                                      bytes_per_el=bytes_per_el)
    prof_decode = profiles_from_arch(model_cfg, seq_len=1,
                                     bytes_per_el=bytes_per_el)
    prefill_flops = sum(p.flops for p in prof_prefill)
    params_bytes = sum(p.params_bytes for p in prof_decode)
    # per-token KV row: every layer's K and V vectors
    kv_tok = (model_cfg.num_layers * 2 * model_cfg.num_kv_heads
              * model_cfg.head_dim * bytes_per_el)
    pages = -(-prompt_len // page_size)
    handoff_bytes = float(pages * page_size * kv_tok)
    # prompt bytes seen in the clear by an untrusted prefill device: the
    # embedded prompt activations (similarity 1.0 at the input by
    # definition — cut_exposure then prices the full volume)
    prompt_bytes = float(prompt_len * model_cfg.d_model * bytes_per_el)
    # mean decode context: KV grows from prompt_len to prompt_len+max_new
    kv_mean = (prompt_len + max_new / 2.0) * kv_tok

    graph = rm.resource_graph()
    cands: List[RoleCandidate] = []
    for pname, pdev in graph.devices.items():
        for dname, ddev in graph.devices.items():
            if not ddev.trusted:
                continue                 # decode stays in the enclave
            n_layers = model_cfg.num_layers
            pre = (prefill_flops / pdev.flops_per_s
                   + n_layers * pdev.per_layer_overhead)
            if pname == dname:
                seal_s = link_s = 0.0    # monolithic: no handoff at all
                interf = max(0, concurrency - 1) * pre
            else:
                seal_s = (seal_time(handoff_bytes, pdev)
                          + seal_time(handoff_bytes, ddev))
                link_s = transmit_time(handoff_bytes,
                                       graph.link(pname, dname))
                interf = 0.0
            dec = max_new * ((params_bytes + kv_mean) / ddev.mem_bw
                             + n_layers * ddev.per_layer_overhead)
            leak = 0.0 if pdev.trusted else cut_exposure(1.0, prompt_bytes)
            cands.append(RoleCandidate(pname, dname, pre, seal_s, link_s,
                                       dec, interf, leak))
    assert cands, "no trusted decode domain registered"
    cands.sort(key=lambda c: (c.latency_s, c.prefill_domain,
                              c.decode_domain))
    best = cands[0]
    return RolePlan(best.prefill_domain, best.decode_domain, best.latency_s,
                    best.leakage, handoff_bytes, tuple(cands))


# ---------------------------------------------------------------------------
# The prefill role
# ---------------------------------------------------------------------------
class PrefillEngine:
    """A ``ServingEngine`` driven prefill-only.

    ``pump()`` runs one admission round — ``_admit`` (bucketed / packed /
    swap-resume prefill) plus one chunked-prefill advance — and then
    exports every slot that reached RUNNING (prompt fully in, first token
    sampled) as a sealed ``TransferManifest``. The engine never takes a
    decode tick: its slots exist only long enough to prefill and seal.
    Requests that *finish at prefill* (``max_new_tokens == 1``, or EOS on
    the first sampled token) complete here and are returned separately —
    nothing is shipped for them."""

    def __init__(self, eng: ServingEngine):
        assert eng.config.disagg_role == "prefill", eng.config.disagg_role
        self.eng = eng
        self._completed_seen = 0

    def pump(self) -> Tuple[List[Tuple[Request, TransferManifest]],
                            List[Request]]:
        """One prefill round. Returns (handoffs, completed_at_prefill)."""
        eng = self.eng
        eng._admit()
        eng._advance_chunks()
        handoffs = []
        for slot, req in list(eng.scheduler.decoding()):
            handoffs.append(eng.export_transfer(slot))
        done = eng.scheduler.completed_total - self._completed_seen
        completed: List[Request] = []
        if done:
            completed = list(eng.scheduler.finished)[-done:]
            self._completed_seen = eng.scheduler.completed_total
        # the prefill clock ticks per pump so queue-wait stats stay
        # meaningful even though no decode step ever runs here
        eng.steps += 1
        return handoffs, completed

    def has_work(self) -> bool:
        return self.eng.scheduler.has_work()

    def check_invariants(self) -> None:
        self.eng.scheduler.check_invariants()
        self.eng.check_page_invariants()


# ---------------------------------------------------------------------------
# The orchestrator
# ---------------------------------------------------------------------------
class DisaggOrchestrator:
    """Routes requests across the prefill/decode engine pair.

    * **rid discipline** — the orchestrator owns the rid counter and adopts
      each Request into the prefill engine's queue with it, so the sampler
      keystreams (keyed ``(rid, token-index)``) are identical to a
      monolithic engine receiving the same submissions in the same order.
    * **back-pressure** — the prefill engine is pumped only while the
      decode scheduler has admission room (queue shorter than its slot
      count); otherwise the round is skipped and counted
      (``backpressure_events``) — prompts wait in the prefill queue, and
      nothing unbounded accumulates in the decode pool's transfer ledger.
    * **delivery ladder** — every handoff rides an in-flight queue with a
      per-handoff deadline and bounded exponential-backoff retries. The
      chaos fault plane (the decode engine's ``faults``) may drop a
      delivery attempt (re-sent after ``2^attempt`` orchestrator ticks,
      ``handoff_retries``), park it for a few ticks (``delay``, counted as
      a ``handoff_redelivery`` when it lands), or corrupt/truncate the
      sealed payload in transit (caught by the integrity digest at
      ``_transfer_in``, which falls back to re-prefill). A handoff that
      exhausts its attempts or blows its deadline demotes to decode-side
      re-prefill (``handoff_reprefills``): the request is adopted WITHOUT
      its manifest and rebuilds KV teacher-forced — bit-identical, never
      dropped. ``decode.pending_external`` mirrors the in-flight count so
      a decode stall behind an outstanding retry classifies as
      recoverable, not permanent.
    * **fallback** — with no prefill peer, ``submit``/``step`` drive the
      decode engine directly: same streams, one engine, zero handoffs.
    """

    # retry ladder bounds: attempt k is re-sent after 2^k ticks, so a
    # handoff is abandoned to re-prefill after ~2^MAX ticks or at its
    # delivery deadline, whichever comes first — worst-case TTFT is bounded
    MAX_ATTEMPTS = 4
    DEADLINE_TICKS = 24

    def __init__(self, decode: ServingEngine,
                 prefill: Optional[PrefillEngine] = None):
        assert decode.config.disagg_role in ("", "decode"), \
            decode.config.disagg_role
        self.decode = decode
        self.prefill = prefill
        if prefill is not None:
            pe = prefill.eng
            assert decode.config.disagg_role == "decode", \
                "decode engine must be built with disagg_role='decode'"
            # bit-identical streams need identical params and sampler config
            assert pe.params is decode.params, \
                "prefill and decode engines must share params"
            for f in ("temperature", "top_k", "sample_seed", "page_size"):
                assert getattr(pe.config, f) == getattr(decode.config, f), \
                    f"prefill/decode config mismatch on {f}"
        self._next_rid = 0
        self.backpressure_events = 0
        self.handoffs = 0
        self.prefill_completed: List[Request] = []
        # in-flight delivery ladder: [req, man, attempt, due, deadline,
        # delayed] rows keyed to the orchestrator tick clock (decode.steps
        # does not advance while decode idles, so retries need their own
        # monotone clock)
        self.clock = 0
        self._in_flight: List[List[Any]] = []

    # -- submission --------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               eos_id: Optional[int] = None) -> Request:
        if self.prefill is None:
            req = self.decode.submit(prompt, max_new_tokens, eos_id)
            self._next_rid = req.rid + 1
            return req
        pe = self.eng_prefill
        assert 1 <= len(prompt) <= pe.config.prompt_capacity, \
            f"prompt length {len(prompt)} > prefill capacity " \
            f"{pe.config.prompt_capacity}"
        total = len(prompt) + max_new_tokens
        for eng, role in ((pe, "prefill"), (self.decode, "decode")):
            assert total <= eng.request_capacity, \
                f"prompt+max_new {total} > {role} request_capacity " \
                f"{eng.request_capacity}"
            worst = eng.pool.pages_needed(total) + 1
            assert worst <= eng.pool.num_pages - 1, \
                f"request needs {worst} pages but the {role} pool holds " \
                f"{eng.pool.num_pages - 1}"
        req = Request(self._next_rid, tuple(int(t) for t in prompt),
                      max_new_tokens, eos_id, submit_step=pe.steps)
        self._next_rid += 1
        pe.scheduler.adopt(req)
        return req

    @property
    def eng_prefill(self) -> ServingEngine:
        assert self.prefill is not None
        return self.prefill.eng

    # -- one orchestrator tick ---------------------------------------------
    def step(self) -> None:
        """Pump prefill (under back-pressure), drive the in-flight delivery
        ladder, tick decode."""
        self.clock += 1
        if self.prefill is not None and self.prefill.has_work():
            room = (len(self.decode.scheduler.queue)
                    + len(self._in_flight)
                    < self.decode.config.num_slots)
            if room:
                handoffs, completed = self.prefill.pump()
                for req, man in handoffs:
                    self._in_flight.append(
                        [req, man, 0, self.clock,
                         self.clock + self.DEADLINE_TICKS, False])
                self.prefill_completed.extend(completed)
            else:
                self.backpressure_events += 1
        self._deliver_due()
        self.decode.pending_external = len(self._in_flight)
        self.decode.step()

    def _deliver_due(self) -> None:
        """One pass over the in-flight ladder: attempt every due delivery.
        Fault-free (no plane, or no firing) every handoff enqueued this
        tick delivers this tick — the ladder adds zero latency and the
        streams/stats match the pre-ladder orchestrator exactly."""
        plane = self.decode.faults
        rec = self.decode.recovery
        still: List[List[Any]] = []
        for entry in self._in_flight:
            req, man, attempt, due, deadline, delayed = entry
            if self.clock < due:
                still.append(entry)
                continue
            if self.clock > deadline:
                # deadline blown (pathological drop/delay streak): demote
                # to decode-side re-prefill rather than retry forever
                self._demote_to_reprefill(req, man, "deadline")
                continue
            fate, d = plane.handoff_fate() if plane is not None \
                else ("deliver", 0)
            if fate == "drop":
                attempt += 1
                if attempt >= self.MAX_ATTEMPTS:
                    self._demote_to_reprefill(req, man, "retries")
                    continue
                rec["handoff_retries"] += 1
                entry[2] = attempt
                entry[3] = self.clock + (1 << attempt)   # exponential backoff
                still.append(entry)
                continue
            if fate == "delay":
                entry[3] = self.clock + d
                entry[5] = True
                still.append(entry)
                continue
            if plane is not None:
                # in-transit tamper site: the damage travels with the
                # manifest; the decode engine's integrity check at
                # _transfer_in catches it and falls back to re-prefill
                tampered, mode = plane.maybe_tamper_transfer(man.payload)
                if mode is not None:
                    man.payload = tampered
            self.decode.ingest_transfer(req, man)
            self.handoffs += 1
            if attempt > 0 or delayed:
                rec["handoff_redeliveries"] += 1
        self._in_flight = still

    def _demote_to_reprefill(self, req: Request, man: TransferManifest,
                             why: str) -> None:
        """Retry exhaustion: abandon the sealed handoff and adopt the bare
        request into the decode queue. ``_prefill_slot`` finds no manifest
        and re-prefills prompt + the prefill role's first token
        teacher-forced — the stream is still bit-identical, the request is
        never lost; only the handoff's O(pages) resume is forfeited."""
        del man      # the sealed payload is abandoned with the delivery
        self.decode.recovery["handoff_reprefills"] += 1
        self.decode.scheduler.adopt(req)
        self.decode._emit("handoff_reprefill", {"rid": req.rid, "why": why})

    def has_work(self) -> bool:
        return ((self.prefill is not None and self.prefill.has_work())
                or bool(self._in_flight)
                or self.decode.scheduler.has_work())

    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        """Drive to completion; returns every finished request (decode-side
        completions plus requests that finished at prefill), rid-sorted."""
        n = 0
        while self.has_work():
            if max_steps is not None and n >= max_steps:
                break
            self.step()
            if self.decode.stalled and not (
                    self.prefill is not None and self.prefill.has_work()):
                break
            n += 1
        out = list(self.decode.scheduler.finished) + self.prefill_completed
        return sorted(out, key=lambda r: r.rid)

    def run_trace(self, arrivals, max_steps: Optional[int] = None
                  ) -> List[Request]:
        """Timed trace replay against the orchestrator clock (the decode
        engine's step counter — same clock ``ServingEngine.run_trace``
        uses), so load_trace presets replay comparably."""
        arrivals = sorted(arrivals, key=lambda a: a[0])
        reqs: List[Request] = []
        k, n = 0, 0
        while k < len(arrivals) or self.has_work():
            if max_steps is not None and n >= max_steps:
                break
            while k < len(arrivals) and arrivals[k][0] <= self.decode.steps:
                _, prompt, max_new, eos = arrivals[k]
                reqs.append(self.submit(list(prompt), max_new, eos_id=eos))
                k += 1
            if not self.has_work():
                self.decode.steps = max(self.decode.steps, arrivals[k][0])
                continue
            self.step()
            if self.decode.stalled and not (
                    self.prefill is not None and self.prefill.has_work()):
                break
            n += 1
        return reqs

    # -- introspection -----------------------------------------------------
    def check_invariants(self) -> None:
        if self.prefill is not None:
            self.prefill.check_invariants()
        self.decode.scheduler.check_invariants()
        self.decode.check_page_invariants()

    def stats(self) -> Dict[str, Any]:
        out = dict(self.decode.stats())
        out["disagg"] = self.prefill is not None
        out["handoffs"] = self.handoffs
        out["in_flight_handoffs"] = len(self._in_flight)
        out["backpressure_events"] = self.backpressure_events
        out["prefill_completed"] = len(self.prefill_completed)
        if self.prefill is not None:
            pe = self.eng_prefill
            out["pending_handoffs"] = self.decode.pool.pending_transfers
            out["prefill_stats"] = {
                "admissions": pe.admissions,
                "prefill_calls": pe.prefill_calls,
                "transfers_out": pe.transfers_out,
                "packed_admissions": pe.packed_admissions,
                "packed_prefills": pe.packed_prefills,
                "queued": len(pe.scheduler.queue),
                "post_warmup_compiles": pe.aot.post_freeze_compiles,
            }
        return out


# ---------------------------------------------------------------------------
# Convenience constructor
# ---------------------------------------------------------------------------
def build_disagg(api, params=None, *, config: Optional[EngineConfig] = None,
                 prefill_overrides: Optional[Dict[str, Any]] = None,
                 backend: Optional[str] = None, mesh=None, rm=None,
                 warmup: Optional[bool] = None) -> DisaggOrchestrator:
    """Build a prefill/decode engine pair over SHARED params and wire the
    orchestrator. ``config`` seeds both engines; ``prefill_overrides``
    (e.g. ``{"prefill_pack": 4, "num_slots": 2}``) reshape the prefill
    role, which typically wants fewer slots and packed prefill. The decode
    engine keeps the full config (its pool must hold steady-state KV)."""
    cfg = config or EngineConfig()
    if params is None:
        params = api.init(jax.random.PRNGKey(0))
    d_cfg = dataclasses.replace(cfg, disagg_role="decode")
    p_over = dict(prefill_overrides or {})
    p_over["disagg_role"] = "prefill"
    p_cfg = dataclasses.replace(cfg, **p_over)
    if warmup is not None:
        d_cfg = dataclasses.replace(d_cfg, warmup=warmup)
        p_cfg = dataclasses.replace(p_cfg, warmup=warmup)
    decode = ServingEngine(api, mesh=mesh, rm=rm, config=d_cfg,
                           params=params, backend=backend)
    pre = ServingEngine(api, mesh=mesh, rm=rm, config=p_cfg,
                        params=params, backend=backend)
    if decode.warmed or pre.warmed:
        # the compile monitor is process-global: the second engine's warmup
        # lands inside the first's post-freeze window — re-snapshot both
        # ledgers now that ALL warmup compilation is done, so
        # post_warmup_compiles counts only steady-state handoff traffic
        decode.aot.freeze()
        pre.aot.freeze()
    return DisaggOrchestrator(decode, PrefillEngine(pre))
