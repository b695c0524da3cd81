"""Serving launcher: continuous-batching Serdab engine over trust-domain pods.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \\
      --mesh 2x2 --stages 2 --microbatches 2 --slots 4 --requests 6

``--stages 1`` serves on one device with no mesh (the one-chip entry
point); more stages build a ``pod x data`` mesh (``--mesh``, default
``<stages>x1``), and a mesh larger than the device count is an error.

Thin CLI over ``repro.serving.ServingEngine`` (DESIGN.md §Serving engine):
plans a ``PlacementSpec`` over the registered trust domains (``--topology``
picks the registry; ``--space segment`` is the default PlacementSpec search,
``--space prefix`` the legacy trusted-prefix tree), serves a synthetic
stream of heterogeneous requests with continuous batching, and optionally
injects a straggler stage (``--inject-straggler STAGE:FACTOR``) to
demonstrate telemetry-driven live re-planning with stage-layout cache
migration. ``--verify-swap`` runs the same request stream twice — with and
without the injected straggler — and asserts the decoded token streams are
identical across the live swap (requires ``--no-seal``: boundary sealing
quantizes whichever activation crosses the cut, so moving the cut moves the
quantization noise). ``--topology sandwich --require-non-prefix`` asserts
the planned spec is NOT expressible in the prefix space (multiple untrusted
segments); ``--temperature``/``--top-k`` switch greedy decoding to
per-request-reproducible sampling.

AOT warmup & chunked prefill (DESIGN.md §AOT warmup & chunked prefill):
``--warmup`` compiles every serving shape at engine construction and
freezes the compile ledger; ``--assert-no-recompile`` then fails the run
if steady-state serving performed ANY new XLA compilation (the zero-
compile-stall guarantee, checked against the runtime's own compile
counter). ``--prefill-chunk N`` streams long prompts in N-token chunks
interleaved with decode ticks (bounded batch-mate inter-token latency);
``--verify-chunked`` serves the same stream again with chunking disabled
and asserts token-identical output (use with ``--f32 --no-seal`` — the
chunked attention path is a different, equally-correct float reduction
order, so bf16 argmax ties may flip).

Two-tier KV swap (DESIGN.md §Two-tier KV & swap): under a tight
``--num-pages`` pool the demand policy preempts; ``--preempt-policy swap``
(the default) seals the victim's pages to a host-side swap tier and
restores them bit-exactly on resume instead of recomputing the prefix.
``--verify-preempt`` reruns the stream under the recompute oracle and
undisturbed (``--num-pages 0``) and asserts all three token streams are
identical (use with ``--f32`` — swap restore is bit-exact, so only float
argmax ties could otherwise differ between resume paths).

Chaos injection (DESIGN.md §Fault injection & recovery): ``--chaos``
arms the deterministic seeded fault plane (`serving/faults.py`) —
sealed-payload tampering, telemetry stage stalls, handoff drop/delay
under ``--disagg``, pool-exhaustion storms, and (``--chaos-death P``)
device death mid-decode. ``--verify-recovery`` reruns the same stream
fault-free and asserts the chaotic run's token streams are identical
AND every injected fault is attributable to a named
``stats()["recovery"]`` counter (use with ``--f32 --no-seal``); with
``--warmup --assert-no-recompile`` the whole recovery ladder is also
proven compile-free.
"""
from __future__ import annotations

import argparse
import copy

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, reduced as reduce_cfg
from repro.core.privacy import LM_SIM_DELTA
from repro.enclave.domain import sandwich_manager, two_enclave_manager
from repro.launch.mesh import make_mesh
from repro.models.api import build_model
from repro.serving import EngineConfig, FaultConfig, ServingEngine
from repro.utils.compile_cache import enable_compile_cache

TOPOLOGIES = {
    "two-enclave": lambda stages: two_enclave_manager(),
    # 1 trusted CC pod + (stages-1) full-rate untrusted pods: the optimal
    # placement pipelines multiple untrusted segments — non-prefix by
    # construction (the legacy space allows only one untrusted suffix)
    "sandwich": lambda stages: sandwich_manager(max(1, stages - 1)),
}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="",
                    help="pod x data (default <stages>x1; built only when "
                         "--stages > 1 and the backend is not local)")
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--slots", type=int, default=4,
                    help="KV-cache slots == decode batch")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="max synthetic prompt length (uniform 2..N)")
    ap.add_argument("--max-new", type=int, default=8,
                    help="tokens to generate per request")
    ap.add_argument("--arrival-every", type=int, default=2,
                    help="submit one request every K engine steps")
    ap.add_argument("--max-seq", type=int, default=0,
                    help="legacy timeline horizon (0 = auto-size)")
    ap.add_argument("--kv-layout", default="paged",
                    choices=["paged", "timeline"],
                    help="paged per-slot KV cache (unbounded lifetime) or "
                         "the legacy shared-position timeline")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged layout)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="shared page-pool size (0 = all slots at full "
                         "request capacity; smaller pools exercise "
                         "admission back-pressure)")
    ap.add_argument("--page-policy", default="demand",
                    choices=["demand", "reserve"],
                    help="demand: allocate pages as generation reaches "
                         "them, COW prefix sharing + preemption on "
                         "exhaustion; reserve: admit only on worst-case "
                         "reservation (PR 5 baseline)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable the COW prefix index (demand policy)")
    ap.add_argument("--preempt-policy", default="auto",
                    choices=["auto", "swap", "recompute"],
                    help="swap: seal victim pages to the host tier and "
                         "restore them on resume (O(pages)); recompute: "
                         "drop pages and re-prefill on resume (PR 6 "
                         "baseline, O(generated tokens)); auto (default): "
                         "swap on the paged layout, recompute on the "
                         "legacy timeline (which cannot swap — asking "
                         "for swap there is a config-time error)")
    ap.add_argument("--no-decode-cow", action="store_true",
                    help="don't register decode-completed pages in the "
                         "COW prefix index")
    ap.add_argument("--verify-preempt", action="store_true",
                    help="serve the stream again under the recompute "
                         "oracle AND undisturbed (roomy pool) and assert "
                         "all three token streams are identical (use with "
                         "--f32; requires --preempt-policy swap and a "
                         "pool tight enough to actually preempt)")
    ap.add_argument("--shared-system-prompt", type=float, default=0.0,
                    metavar="RATIO",
                    help="fraction of synthetic prompts extending one "
                         "fixed system prompt (drives COW page sharing)")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile every serving shape (decode step, "
                         "all prefill buckets, page ops, chunk kernel, "
                         "swap-target layouts) before serving")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="stream prompts longer than N in N-token chunks, "
                         "one chunk per engine step between decode ticks "
                         "(0 = whole-prompt prefill)")
    ap.add_argument("--assert-no-recompile", action="store_true",
                    help="with --warmup: fail unless steady-state serving "
                         "performed zero new XLA compilations")
    ap.add_argument("--verify-chunked", action="store_true",
                    help="with --prefill-chunk: serve the stream again "
                         "unchunked and assert identical token streams")
    ap.add_argument("--per-token-prefill", action="store_true",
                    help="disable one-call batched prefill (admission-"
                         "latency baseline)")
    ap.add_argument("--prefill-pack", type=int, default=0,
                    help="pack up to K queued short prompts into ONE "
                         "bucketed prefill call (paged + batched prefill "
                         "only; 0 = off)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated serving: a prefill-role engine "
                         "seals each prompt's KV pages and hands them to "
                         "a decode-role engine over the transfer-manifest "
                         "protocol (paged layout only)")
    ap.add_argument("--verify-disagg", action="store_true",
                    help="with --disagg: serve the same stream three ways "
                         "— disaggregated, monolithic, and orchestrator-"
                         "fallback (no prefill peer) — and assert all "
                         "three token streams are identical (use with "
                         "--f32)")
    ap.add_argument("--chaos", action="store_true",
                    help="arm the seeded chaos fault plane "
                         "(FaultConfig.chaos(seed=--seed)): sealed-"
                         "payload corruption/truncation, stage stalls, "
                         "handoff drops/delays (--disagg), pool storms")
    ap.add_argument("--chaos-death", type=float, default=0.0,
                    metavar="P",
                    help="with --chaos: per-telemetry-tick probability "
                         "of killing a staged device (capped at one "
                         "death; recovery = spill + replan + swap-in)")
    ap.add_argument("--verify-recovery", action="store_true",
                    help="with --chaos: serve the same stream fault-free "
                         "and assert identical token streams AND every "
                         "injected fault accounted to a recovery "
                         "counter (use with --f32 --no-seal)")
    ap.add_argument("--no-seal", action="store_true")
    ap.add_argument("--topology", default="two-enclave",
                    choices=sorted(TOPOLOGIES),
                    help="trust-domain registry the planner places over")
    ap.add_argument("--delta", type=float, default=LM_SIM_DELTA,
                    help="privacy threshold for untrusted segments")
    ap.add_argument("--require-non-prefix", action="store_true",
                    help="assert the planned PlacementSpec is NOT "
                         "expressible in the legacy trusted-prefix space")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the top-k logits (0 = off)")
    ap.add_argument("--solver", default="dp",
                    choices=["dp", "exhaustive", "beam"])
    ap.add_argument("--space", default="segment",
                    choices=["segment", "prefix"],
                    help="placement search space (segment = PlacementSpec)")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "local", "pipelined"])
    ap.add_argument("--telemetry-interval", type=int, default=4)
    ap.add_argument("--inject-straggler", default=None, metavar="STAGE:FACTOR",
                    help="multiply stage STAGE's measured time by FACTOR")
    ap.add_argument("--verify-swap", action="store_true",
                    help="run twice (with/without straggler) and assert "
                         "identical token streams across the live swap")
    ap.add_argument("--f32", action="store_true",
                    help="run in float32 (used with --verify-swap)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def _make_config(args):
    max_seq = args.max_seq or (
        args.prompt_len + args.requests * args.arrival_every
        + args.max_new * args.requests // args.slots + args.max_new + 16)
    ec = EngineConfig(
        num_slots=args.slots, num_stages=args.stages,
        num_microbatches=args.microbatches, max_seq=max_seq,
        prompt_capacity=args.prompt_len,
        kv_layout=args.kv_layout, page_size=args.page_size,
        num_pages=args.num_pages, page_policy=args.page_policy,
        prefix_sharing=not args.no_prefix_sharing,
        preempt_policy=args.preempt_policy,
        decode_cow=not args.no_decode_cow,
        request_capacity=args.prompt_len + args.max_new,
        batched_prefill=not args.per_token_prefill,
        prefill_pack=args.prefill_pack,
        seal_boundary=not args.no_seal, solver=args.solver,
        space=args.space, delta=args.delta,
        temperature=args.temperature, top_k=args.top_k,
        telemetry_interval=args.telemetry_interval,
        warmup=args.warmup, prefill_chunk=args.prefill_chunk,
        faults=(FaultConfig.chaos(seed=args.seed,
                                  device_death=args.chaos_death)
                if args.chaos else None))
    backend = None if args.backend == "auto" else args.backend
    rm = TOPOLOGIES[args.topology](args.stages)
    return ec, backend, rm


def _assert_recovery_accounted(st):
    """Every injected fault maps to a named recovery rung or an
    in-progress marker (the tests/test_faults.py accounting property)."""
    inj, rec, pend = st["faults"], st["recovery"], st["faults_pending"]
    assert inj["corrupt_swap"] + inj["truncate_swap"] \
        == rec["unseal_fallback_swap"], (inj, rec)
    assert inj["corrupt_transfer"] + inj["truncate_transfer"] \
        == rec["unseal_fallback_transfer"], (inj, rec)
    assert inj["device_death"] \
        == rec["device_loss_replans"] + (1 if pend["death"] else 0), (inj, rec)
    assert inj["stage_stall"] \
        == rec["stall_replans"] + (1 if pend["stall"] else 0), (inj, rec)
    assert inj["pool_storm"] \
        == rec["storm_reclaims"] + (1 if pend["storm"] else 0), (inj, rec)


def _make_engine(api, params, mesh, args) -> ServingEngine:
    ec, backend, rm = _make_config(args)
    return ServingEngine(api, mesh=mesh, rm=rm, config=ec, params=params,
                         backend=backend)


def _gen_prompts(args, cfg):
    rng = np.random.RandomState(args.seed)
    sys_prompt = rng.randint(0, cfg.vocab_size,
                             size=max(2, args.prompt_len // 2)).tolist()
    prompts = []
    for _ in range(args.requests):
        if rng.rand() < args.shared_system_prompt:
            tail = rng.randint(
                0, cfg.vocab_size,
                size=int(rng.randint(0, args.prompt_len
                                     - len(sys_prompt) + 1))).tolist()
            prompts.append(sys_prompt + tail)
        else:
            prompts.append(rng.randint(
                0, cfg.vocab_size,
                size=int(rng.randint(2, args.prompt_len + 1))).tolist())
    return prompts


def _serve_stream(eng: ServingEngine, args, cfg):
    """Submit a deterministic synthetic arrival stream and drain it."""
    prompts = _gen_prompts(args, cfg)
    reqs = []
    k = 0
    while k < len(prompts) or eng.scheduler.has_work():
        if k < len(prompts) and eng.steps % args.arrival_every == 0:
            reqs.append(eng.submit(prompts[k], args.max_new))
            k += 1
        moved = eng.step()
        if eng.stalled:
            # permanent back-pressure (legacy timeline exhausted): the FIFO
            # head can never run, so later submissions can't either
            _raise_stalled(reqs, len(prompts), eng.stall_reason)
        if k < len(prompts) and not moved and not eng.scheduler.has_work():
            # idle tick with arrivals pending: admit next immediately
            reqs.append(eng.submit(prompts[k], args.max_new))
            k += 1
    return reqs


def _raise_stalled(reqs, n_prompts: int, reason) -> None:
    done = sum(1 for r in reqs if r.status == "done")
    raise RuntimeError(f"engine stalled ({reason}): {done} of {n_prompts} "
                       f"requests finished, {len(reqs)} submitted")


def _serve_stream_orch(orch, args, cfg):
    """The orchestrator twin of ``_serve_stream`` (same prompt stream, same
    submission order — so rids, and therefore sampler keystreams, match a
    monolithic run exactly)."""
    prompts = _gen_prompts(args, cfg)
    reqs, k = [], 0
    while k < len(prompts) or orch.has_work():
        if k < len(prompts) and orch.decode.steps % args.arrival_every == 0:
            reqs.append(orch.submit(prompts[k], args.max_new))
            k += 1
        orch.step()
        if orch.decode.stalled and not (
                orch.prefill is not None and orch.prefill.has_work()):
            _raise_stalled(reqs, len(prompts), orch.decode.stall_reason)
        if k < len(prompts) and not orch.has_work():
            reqs.append(orch.submit(prompts[k], args.max_new))
            k += 1
    return reqs


def _disagg_main(api, params, mesh, args, cfg):
    """--disagg: serve through the prefill/decode orchestrator; with
    --verify-disagg, re-serve monolithically AND through the no-peer
    fallback orchestrator and assert all three streams are identical."""
    from repro.serving import (DisaggOrchestrator, build_disagg,
                               plan_disagg_roles)
    ec, backend, rm = _make_config(args)
    plan = plan_disagg_roles(rm, cfg, prompt_len=max(args.prompt_len, 16),
                             max_new=args.max_new,
                             page_size=args.page_size)
    print(f"role plan: {plan.describe()}")
    orch = build_disagg(api, params=params, config=ec, backend=backend,
                        mesh=mesh, rm=rm)
    print(f"disagg: prefill backend={orch.eng_prefill.backend_kind} "
          f"decode backend={orch.decode.backend_kind} "
          f"kv_layout={orch.decode.kv_layout}")
    reqs = _serve_stream_orch(orch, args, cfg)
    orch.check_invariants()
    st = orch.stats()
    print(f"served {st['completed'] + st['prefill_completed']} requests, "
          f"{st['tokens_out']} tokens ({st['tok_per_s']:.1f} tok/s) "
          f"handoffs={st['handoffs']} "
          f"backpressure={st['backpressure_events']} "
          f"finished_at_prefill={st['prefill_completed']}")
    ps = st["prefill_stats"]
    print(f"prefill side: admissions={ps['admissions']} "
          f"prefill_calls={ps['prefill_calls']} "
          f"transfers_out={ps['transfers_out']} "
          f"packed={ps['packed_admissions']}")
    if reqs:
        print("sample tokens:", reqs[0].generated)

    if args.assert_no_recompile:
        assert args.warmup, "--assert-no-recompile needs --warmup"
        for side, n, stalls in (
                ("decode", st["post_warmup_compiles"], st["compile_stalls"]),
                ("prefill", ps["post_warmup_compiles"],
                 orch.eng_prefill.stats()["compile_stalls"])):
            assert n == 0, \
                f"{side}: {n} XLA compilations after warmup (stalls: " \
                f"{stalls})"
            assert not stalls, (side, stalls)
        print("NO-RECOMPILE OK: zero post-warmup compiles on both roles")

    if args.verify_disagg:
        mono_reqs = _serve_stream(_make_engine(api, params, mesh, args),
                                  args, cfg)
        fb = DisaggOrchestrator(_make_engine(api, params, mesh, args))
        fb_reqs = _serve_stream_orch(fb, args, cfg)
        assert fb.stats()["handoffs"] == 0
        for a, b, c in zip(reqs, mono_reqs, fb_reqs):
            assert a.generated == b.generated == c.generated, \
                f"req {a.rid} diverged across serving modes:\n" \
                f"  disagg     {a.generated}\n  monolithic {b.generated}\n" \
                f"  fallback   {c.generated}"
        print(f"DISAGG-EXACT OK: {len(reqs)} token streams identical "
              f"across disaggregated / monolithic / fallback "
              f"({st['handoffs']} sealed handoffs)")

    if args.chaos:
        dst = orch.decode.stats()
        print(f"chaos: injected={orch.decode.faults.snapshot()} "
              f"recovery={ {k: v for k, v in dst['recovery'].items() if v} }"
              f" in_flight={st['in_flight_handoffs']}")
    if args.verify_recovery:
        assert args.chaos, "--verify-recovery needs --chaos"
        dst = orch.decode.stats()
        total = orch.decode.faults.total_injected() + \
            orch.eng_prefill.faults.total_injected()
        assert total > 0, "chaos armed but no fault landed"
        assert st["in_flight_handoffs"] == 0
        calm = copy.copy(args)
        calm.chaos = False
        ec2, backend2, rm2 = _make_config(calm)
        orch2 = build_disagg(api, params=params, config=ec2,
                             backend=backend2, mesh=mesh, rm=rm2)
        reqs_calm = _serve_stream_orch(orch2, calm, cfg)
        for a, b in zip(reqs, reqs_calm):
            assert a.generated == b.generated, \
                f"req {a.rid} diverged under chaos:\n" \
                f"  chaotic    {a.generated}\n  fault-free {b.generated}"
        assert not dst["failed_requests"], dst["failed_requests"]
        _assert_recovery_accounted(dst)
        _assert_recovery_accounted(orch.eng_prefill.stats())
        print(f"RECOVERY-EXACT OK: {len(reqs)} token streams identical "
              f"under {total} injected faults across both roles "
              f"({ {k: v for k, v in dst['recovery'].items() if v} })")
    return st


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    if args.f32:
        import repro.models.layers as L
        L.DEFAULT_DTYPE = jnp.float32

    mesh = None
    if args.stages > 1 and args.backend != "local":
        dims = tuple(int(x) for x in (args.mesh or f"{args.stages}x1")
                     .split("x"))
        mesh = make_mesh(dims, ("pod", "data")[:len(dims)])

    api = build_model(cfg, max_seq=args.max_seq or 512)
    params = api.init(jax.random.PRNGKey(0))
    if args.f32:
        params = jax.tree.map(
            lambda x: x.astype(jnp.float32)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)

    if args.disagg:
        return _disagg_main(api, params, mesh, args, cfg)

    inject = None
    if args.inject_straggler:
        s, f = args.inject_straggler.split(":")
        inject = (int(s), float(f))

    def one_run(with_inject: bool, run_args=None):
        a = run_args or args
        eng = _make_engine(api, params, mesh, a)
        if with_inject and inject:
            eng.telemetry.inject(*inject)
        print(f"backend={eng.backend_kind} kv_layout={eng.kv_layout} "
              f"stage_blocks={eng.stage_blocks} "
              f"placement={eng.spec.describe()}")
        if eng.warmed:
            print(f"warmup: {eng.warmup_s:.2f}s, "
                  f"{sum(len(f.signatures) for f in eng.aot.fns.values())} "
                  f"signatures over {len(eng.aot.fns)} functions")
        if args.require_non_prefix:
            graph = eng.rm.resource_graph()
            assert not eng.spec.is_prefix(graph), \
                f"planned placement is prefix-expressible: " \
                f"{eng.spec.describe()}"
            print("NON-PREFIX OK: placement not expressible in the "
                  "trusted-prefix space")
        reqs = _serve_stream(eng, a, cfg)
        for e in eng.events:
            if e.kind in ("replan", "swap", "swap_skipped"):
                print(f"  step {e.step}: {e.kind} {e.detail}")
        st = eng.stats()
        print(f"served {st['completed']} requests, {st['tokens_out']} tokens "
              f"in {st['decode_wall_s']:.2f}s decode "
              f"({st['tok_per_s']:.1f} tok/s), replans={st['replans']} "
              f"swaps={st['swaps']} final_blocks={st['stage_blocks']} "
              f"prefill_calls={st['prefill_calls']} "
              f"admission_p50={st.get('admission_p50_ms', 0):.1f}ms")
        if st.get("swap_outs") or st.get("preemptions"):
            print(f"preempt: policy={st['preempt_policy']} "
                  f"preemptions={st['preemptions']} "
                  f"swap_outs={st['swap_outs']} swap_ins={st['swap_ins']} "
                  f"fallbacks={st['swap_fallbacks']}")
        if st.get("prefill_chunk"):
            print(f"chunked prefill: {st['chunked_admissions']} admissions "
                  f"in {st['prefill_chunks']} chunks of "
                  f"{st['prefill_chunk']} tokens")
        if eng.warmed:
            print(f"post-warmup compiles: {st['post_warmup_compiles']} "
                  f"stalls: {st['compile_stalls']}")
        if eng.faults is not None:
            print(f"chaos: injected={eng.faults.snapshot()} "
                  f"recovery={ {k: v for k, v in st['recovery'].items() if v} }"
                  f" pending={st['faults_pending']} "
                  f"failed={st['failed_requests']}")
        return eng, reqs

    eng, reqs = one_run(with_inject=True)
    st = eng.stats()
    if reqs:
        print("sample tokens:", reqs[0].generated)

    if args.assert_no_recompile:
        # checked BEFORE any --verify-* rerun: the compile counter is
        # process-global, so a second engine's warmup would land in this
        # engine's post-freeze window
        assert args.warmup, "--assert-no-recompile needs --warmup"
        n = st["post_warmup_compiles"]
        assert n == 0, \
            f"{n} XLA compilations after warmup; stalls: " \
            f"{st['compile_stalls']}"
        assert not st["compile_stalls"], st["compile_stalls"]
        print(f"NO-RECOMPILE OK: post_warmup_compiles={n}, 0 stalls")

    if args.verify_swap:
        assert args.no_seal, "--verify-swap needs --no-seal (see docstring)"
        assert inject, "--verify-swap needs --inject-straggler"
        eng2, reqs2 = one_run(with_inject=False)
        assert eng.swaps >= 1, \
            f"straggler injection produced no live swap (events: " \
            f"{[e.kind for e in eng.events]})"
        for a, b in zip(reqs, reqs2):
            assert a.generated == b.generated, \
                f"req {a.rid} diverged across live swap:\n  {a.generated}\n" \
                f"  {b.generated}"
        print(f"SWAP-EXACT OK: {len(reqs)} token streams identical across "
              f"live re-plan ({eng.stats()['stage_blocks']} vs "
              f"{eng2.stats()['stage_blocks']})")

    if args.verify_chunked:
        assert args.prefill_chunk > 0, \
            "--verify-chunked needs --prefill-chunk N"
        unchunked = copy.copy(args)
        unchunked.prefill_chunk = 0
        eng3, reqs3 = one_run(with_inject=True, run_args=unchunked)
        assert eng.stats()["chunked_admissions"] > 0, \
            "no prompt exceeded --prefill-chunk: nothing verified " \
            "(raise --prompt-len or lower --prefill-chunk)"
        for a, b in zip(reqs, reqs3):
            assert a.generated == b.generated, \
                f"req {a.rid} diverged under chunked prefill:\n" \
                f"  {a.generated}\n  {b.generated}"
        print(f"CHUNK-EXACT OK: {len(reqs)} token streams identical, "
              f"chunked ({args.prefill_chunk}) vs one-shot prefill")

    if args.verify_preempt:
        assert args.preempt_policy == "swap", \
            "--verify-preempt compares the swap path against its oracles"
        assert st.get("swap_outs", 0) > 0, \
            "pool never swap-preempted: nothing verified " \
            "(shrink --num-pages or raise --requests)"
        oracle = copy.copy(args)
        oracle.preempt_policy = "recompute"
        _, reqs_rc = one_run(with_inject=True, run_args=oracle)
        roomy = copy.copy(args)
        roomy.num_pages = 0      # all slots at full capacity: no preemption
        eng_ud, reqs_ud = one_run(with_inject=True, run_args=roomy)
        assert eng_ud.stats().get("preemptions", 0) == 0
        for a, b, c in zip(reqs, reqs_rc, reqs_ud):
            assert a.generated == b.generated == c.generated, \
                f"req {a.rid} diverged across preempt policies:\n" \
                f"  swap      {a.generated}\n  recompute {b.generated}\n" \
                f"  undisturbed {c.generated}"
        print(f"PREEMPT-EXACT OK: {len(reqs)} token streams identical "
              f"across swap resume / recompute oracle / undisturbed "
              f"({st['swap_outs']} swap-outs)")

    if args.verify_recovery:
        assert args.chaos, "--verify-recovery needs --chaos"
        total = eng.faults.total_injected()
        assert total > 0, \
            "chaos armed but no fault landed: nothing verified (raise " \
            "--requests, shrink --num-pages, or set --chaos-death)"
        calm = copy.copy(args)
        calm.chaos = False
        _, reqs_calm = one_run(with_inject=True, run_args=calm)
        for a, b in zip(reqs, reqs_calm):
            assert a.generated == b.generated, \
                f"req {a.rid} diverged under chaos:\n" \
                f"  chaotic    {a.generated}\n  fault-free {b.generated}"
        assert not st["failed_requests"], st["failed_requests"]
        _assert_recovery_accounted(st)
        print(f"RECOVERY-EXACT OK: {len(reqs)} token streams identical "
              f"under {total} injected faults, every fault accounted "
              f"({ {k: v for k, v in st['recovery'].items() if v} })")
    return st


if __name__ == "__main__":
    main()
