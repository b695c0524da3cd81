"""Chaos-injection fault plane + request-level recovery (PR 10).

The contract under test (DESIGN.md §Fault injection & recovery): under any
seeded fault schedule — device death mid-decode, stage stalls, sealed
payload corruption/truncation, disagg handoff drops/delays, pool-exhaustion
storms — every admitted request either completes with a token stream
bit-identical to the fault-free run or is surfaced as an explicit
per-request failure, and every injected fault is attributable to a named
recovery counter (``stats()["recovery"]``) or an in-progress marker
(``stats()["faults_pending"]``). Never a silent drop, never a corrupt
token.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.enclave import sealing
from repro.serving.faults import FaultConfig, FaultPlane
from repro.serving.scheduler import DONE


@pytest.fixture(scope="module")
def f32():
    """Exact token comparisons need f32 end to end (params AND caches)."""
    import repro.models.layers as L
    old = L.DEFAULT_DTYPE
    L.DEFAULT_DTYPE = jnp.float32
    yield
    L.DEFAULT_DTYPE = old


@pytest.fixture(scope="module")
def setup(f32):
    from repro.models.api import build_model
    cfg = reduced(get_arch("llama3.2-1b"))
    api = build_model(cfg, max_seq=128)
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x,
        api.init(jax.random.PRNGKey(0)))
    return cfg, api, params


def _engine(api, params, **overrides):
    from repro.serving import EngineConfig, ServingEngine
    kw = dict(num_slots=4, num_microbatches=2, max_seq=128,
              prompt_capacity=16, request_capacity=24,
              telemetry_interval=4, seal_boundary=False, page_size=4,
              page_policy="demand", preempt_policy="swap",
              allow_swap=False)
    kw.update(overrides)
    return ServingEngine(api, config=EngineConfig(**kw), params=params,
                         backend="local")


def _drive_checked(eng, wl, max_steps=900):
    """Submit with arrival gaps; audit scheduler + pool + manifest
    invariants after EVERY step (the per-fault audit the tentpole asks
    for: faults land mid-run, so auditing each step covers each fault);
    drain and assert every request completed or was explicitly failed."""
    reqs, k, gap = [], 0, 0
    while k < len(wl) or eng.scheduler.has_work():
        if k < len(wl) and gap <= 0:
            prompt, max_new, eos, gap = wl[k]
            reqs.append(eng.submit(prompt, max_new, eos_id=eos))
            k += 1
        else:
            gap -= 1
        eng.step()
        eng.scheduler.check_invariants()
        eng.check_page_invariants()
        assert eng.steps < max_steps, "schedule failed to drain"
    failed = eng.stats()["failed_requests"]
    for r in reqs:
        assert r.status == DONE or r.rid in failed, (r.rid, r.status)
    return [r.generated for r in reqs]


def _assert_accounted(eng):
    """Every injected fault maps to a recovery rung or pending marker."""
    st = eng.stats()
    inj, rec, pend = st["faults"], st["recovery"], st["faults_pending"]
    assert inj["corrupt_swap"] + inj["truncate_swap"] \
        == rec["unseal_fallback_swap"]
    assert inj["corrupt_transfer"] + inj["truncate_transfer"] \
        == rec["unseal_fallback_transfer"]
    assert inj["device_death"] \
        == rec["device_loss_replans"] + (1 if pend["death"] else 0)
    assert inj["stage_stall"] \
        == rec["stall_replans"] + (1 if pend["stall"] else 0)
    assert inj["pool_storm"] \
        == rec["storm_reclaims"] + (1 if pend["storm"] else 0)


# ---------------------------------------------------------------------------
# Integrity tags: the malleable XOR cipher gap, closed
# ---------------------------------------------------------------------------
def test_payload_digest_detects_bit_flip():
    payload = (np.arange(24, dtype=np.float32).reshape(3, 8),
               np.ones((3, 8), np.float32))
    d = sealing.payload_digest(payload)
    sealing.verify_payload(payload, d)          # clean round trip
    bad = (payload[0].copy(), payload[1])
    bad[0].reshape(-1).view(np.uint8)[5] ^= 1   # one flipped bit
    with pytest.raises(sealing.SealIntegrityError):
        sealing.verify_payload(bad, d)


def test_payload_digest_detects_truncation():
    payload = (np.arange(24, dtype=np.float32).reshape(3, 8),)
    d = sealing.payload_digest(payload)
    with pytest.raises(sealing.SealIntegrityError, match="mismatch"):
        sealing.verify_payload((payload[0][:2],), d)


def test_verify_payload_none_digest_is_trivial():
    """Untagged manifests (hand-built in tests, pre-PR-10 callers) verify
    trivially — the tag is an opt-in commitment, not a format change."""
    sealing.verify_payload((np.zeros(4),), None)


# ---------------------------------------------------------------------------
# FaultPlane: determinism + site semantics
# ---------------------------------------------------------------------------
def test_fault_plane_deterministic_replay():
    cfg = FaultConfig.chaos(seed=9, device_death=0.3, pool_storm=0.2)
    a, b = FaultPlane(cfg), FaultPlane(cfg)
    trace_a = [(a.pick_device_death(["p0", "p1"]), a.pick_stage_stall(3),
                a.handoff_fate(), a.storm_pages(16)) for _ in range(50)]
    trace_b = [(b.pick_device_death(["p0", "p1"]), b.pick_stage_stall(3),
                b.handoff_fate(), b.storm_pages(16)) for _ in range(50)]
    assert trace_a == trace_b
    assert a.snapshot() == b.snapshot()
    a.reset()
    assert a.total_injected() == 0 and a.device_deaths == 0


def test_tamper_modifies_copies_and_counts():
    plane = FaultPlane(FaultConfig(seed=1, corrupt_swap=1.0))
    orig = (np.zeros((4, 8), np.float32), np.zeros((4, 8), np.float32))
    out, mode = plane.maybe_tamper_swap(orig)
    assert mode == "corrupt" and plane.injected["corrupt_swap"] == 1
    # exactly one bit differs, and the original buffers are untouched
    diff = sum(np.sum(a != b) for a, b in zip(orig, out))
    assert diff == 1 and not orig[0].any() and not orig[1].any()
    plane2 = FaultPlane(FaultConfig(seed=1, truncate_swap=1.0))
    out2, mode2 = plane2.maybe_tamper_swap(orig)
    assert mode2 == "truncate" and out2[0].shape[0] == 3


def test_device_death_capped_and_storm_bounded():
    plane = FaultPlane(FaultConfig(seed=0, device_death=1.0,
                                   max_device_deaths=1, pool_storm=1.0,
                                   storm_fraction=1.0))
    assert plane.pick_device_death(["a", "b"]) in ("a", "b")
    assert plane.pick_device_death(["a", "b"]) is None   # cap reached
    assert plane.pick_device_death([]) is None           # no survivors
    # a storm never seizes the whole free list
    assert plane.storm_pages(3) == 0
    assert 0 < plane.storm_pages(10) <= 8


# ---------------------------------------------------------------------------
# Recovery rungs, one at a time (seeded, deterministic)
# ---------------------------------------------------------------------------
def _workload(rng, vocab, n, lo=4, hi=13):
    return [(rng.randint(1, vocab, size=int(rng.randint(3, 9))).tolist(),
             int(rng.randint(lo, hi)), None, int(rng.randint(0, 2)))
            for _ in range(n)]


def test_swap_tamper_recompute_fallback_bit_identical(setup):
    """Every tampered swap payload is caught by the integrity digest and
    demoted to recompute — streams match the fault-free run exactly."""
    cfg, api, params = setup
    rng = np.random.RandomState(2)
    wl = _workload(rng, cfg.vocab_size, 8, lo=8, hi=17)
    base = _drive_checked(_engine(api, params, num_pages=12), wl)
    eng = _engine(api, params, num_pages=12,
                  faults=FaultConfig(seed=7, corrupt_swap=0.7,
                                     truncate_swap=0.3))
    got = _drive_checked(eng, wl)
    assert got == base
    st = eng.stats()
    assert st["recovery"]["unseal_fallback_swap"] > 0
    assert not eng.pool.swap_manifest
    _assert_accounted(eng)


def test_device_death_spill_replan_resume_bit_identical(setup):
    """Device loss mid-decode: active slots spill to sealed host manifests,
    the placement re-solves around the corpse (failure_replans names it),
    and every victim resumes bit-identically."""
    cfg, api, params = setup
    rng = np.random.RandomState(3)
    wl = _workload(rng, cfg.vocab_size, 6)
    base = _drive_checked(_engine(api, params), wl)
    eng = _engine(api, params,
                  faults=FaultConfig(seed=5, device_death=1.0,
                                     max_device_deaths=1))
    got = _drive_checked(eng, wl)
    assert got == base
    st = eng.stats()
    assert st["faults"]["device_death"] == 1
    assert st["recovery"]["device_loss_replans"] == 1
    assert st["recovery"]["device_loss_spills"] > 0
    assert st["failure_replans"] == 1 and len(st["excluded_devices"]) == 1
    _assert_accounted(eng)


def test_pool_storm_recovered_and_audited(setup):
    """Storms seize free pages mid-run; timers / the deadlock breaker hand
    them back; the pool audit passes at every step with the seized pages
    accounted as live references."""
    cfg, api, params = setup
    rng = np.random.RandomState(4)
    wl = _workload(rng, cfg.vocab_size, 8)
    base = _drive_checked(_engine(api, params, num_pages=16), wl)
    eng = _engine(api, params, num_pages=16,
                  faults=FaultConfig(seed=2, pool_storm=0.3,
                                     storm_fraction=0.7, storm_steps=3))
    got = _drive_checked(eng, wl)
    assert got == base
    st = eng.stats()
    assert st["faults"]["pool_storm"] > 0
    assert st["recovery"]["storm_reclaims"] > 0
    assert st["free_pages"] > 0           # nothing leaked to the storm
    _assert_accounted(eng)


def test_stall_classification_recoverable_vs_permanent(setup):
    """Satellite bugfix: a stall behind a pending recovery mechanism
    (storm pages the deadlock breaker will reclaim, in-flight handoff
    retries) never surfaces as permanent; only a stall nothing in the
    engine can unblock reports ``stall_reason == "permanent"``."""
    cfg, api, params = setup
    # storm seizure wedging admission: the deadlock breaker reclaims the
    # seized pages in the SAME step, so the head admits without the engine
    # ever reporting a (false) permanent stall
    eng = _engine(api, params, num_pages=10)
    eng.faults = FaultPlane(FaultConfig(seed=0))
    pages = eng.pool.alloc(eng.pool.free_pages - 1)
    eng._storm_pages = pages
    eng._storm_left = 10**9               # timer never expires in this test
    req = eng.submit([1, 2, 3, 4, 5, 6, 7, 8], 4)
    eng.step()
    assert not eng.stalled and eng.stall_reason is None
    assert eng.recovery["storm_reclaims"] == 1
    eng.run(max_steps=60)
    assert req.status == DONE

    # permanent: pages held by something no engine mechanism can reclaim
    eng2 = _engine(api, params, num_pages=10)
    held = eng2.pool.alloc(eng2.pool.free_pages - 1)
    assert held is not None
    req2 = eng2.submit([1, 2, 3, 4, 5, 6, 7, 8], 4)
    eng2.step()
    assert eng2.stalled and eng2.stall_reason == "permanent"
    assert eng2.stats()["stall_reason"] == "permanent"
    # ... unless an orchestrator reports in-flight work for this engine
    # (disagg handoff retries): the same wedge flips to recoverable
    eng2.stalled = False
    eng2.pending_external = 1
    eng2.step()
    assert not eng2.stalled and eng2.stall_reason == "recoverable"
    assert eng2.stats()["pending_external"] == 1
    assert req2.status != DONE            # still parked, but not abandoned


# ---------------------------------------------------------------------------
# Disagg handoff ladder: drop / delay / corrupt / demote
# ---------------------------------------------------------------------------
def _disagg(api, params, faults=None):
    import dataclasses as dc

    from repro.serving import EngineConfig, build_disagg
    cfg = EngineConfig(num_slots=4, num_microbatches=2, max_seq=128,
                       prompt_capacity=16, request_capacity=24,
                       telemetry_interval=4, seal_boundary=False,
                       page_size=4, warmup=False, allow_swap=False,
                       faults=faults)
    return build_disagg(api, params, config=cfg, backend="local")


def _run_disagg(orch, wl, max_steps=900):
    reqs = [orch.submit(p, m, eos_id=e) for p, m, e, _gap in wl]
    n = 0
    while orch.has_work():
        orch.step()
        orch.check_invariants()
        n += 1
        assert n < max_steps, "disagg schedule failed to drain"
    failed = orch.decode.stats()["failed_requests"]
    for r in reqs:
        assert r.status == DONE or r.rid in failed, (r.rid, r.status)
    return [r.generated for r in reqs]


def test_handoff_drop_exhausts_retries_then_reprefills(setup):
    """With every delivery attempt dropped, each handoff burns its retry
    budget and demotes to decode-side re-prefill — streams still match the
    fault-free orchestrator; nothing is lost."""
    cfg, api, params = setup
    rng = np.random.RandomState(6)
    wl = _workload(rng, cfg.vocab_size, 5)
    base = _run_disagg(_disagg(api, params), wl)
    orch = _disagg(api, params, faults=FaultConfig(seed=1,
                                                   drop_handoff=1.0))
    got = _run_disagg(orch, wl)
    assert got == base
    rec = orch.decode.recovery
    n = len(wl)
    assert rec["handoff_reprefills"] == n
    assert rec["handoff_retries"] == n * (orch.MAX_ATTEMPTS - 1)
    assert not orch._in_flight and orch.decode.pending_external == 0


def test_handoff_chaos_mix_bit_identical(setup):
    """Drops, delays, and in-transit corruption together: retried and
    redelivered handoffs land, corrupted ones fall back to re-prefill via
    the integrity digest, and every stream matches fault-free."""
    cfg, api, params = setup
    rng = np.random.RandomState(8)
    wl = _workload(rng, cfg.vocab_size, 8)
    base = _run_disagg(_disagg(api, params), wl)
    orch = _disagg(api, params, faults=FaultConfig(
        seed=11, drop_handoff=0.4, delay_handoff=0.3,
        corrupt_transfer=0.4, truncate_transfer=0.2))
    got = _run_disagg(orch, wl)
    assert got == base
    eng = orch.decode
    inj = eng.faults.snapshot()
    rec = eng.recovery
    assert inj["corrupt_transfer"] + inj["truncate_transfer"] \
        == rec["unseal_fallback_transfer"]
    if inj["drop_handoff"]:
        assert rec["handoff_retries"] + rec["handoff_reprefills"] > 0
    if inj["delay_handoff"]:
        assert rec["handoff_redeliveries"] + rec["handoff_reprefills"] > 0
    assert not orch._in_flight


# ---------------------------------------------------------------------------
# THE property: random fault schedules ≡ fault-free oracle
# ---------------------------------------------------------------------------
def _chaos_paged_case(setup, seed, fault_seed, num_pages, death):
    cfg, api, params = setup
    rng = np.random.RandomState(seed)
    wl = _workload(rng, cfg.vocab_size, int(rng.randint(4, 9)),
                   lo=6, hi=16)
    base = _drive_checked(_engine(api, params, num_pages=num_pages), wl)
    chaos = FaultConfig.chaos(
        seed=fault_seed, pool_storm=0.15,
        device_death=0.5 if death else 0.0)
    eng = _engine(api, params, num_pages=num_pages, faults=chaos)
    got = _drive_checked(eng, wl)
    assert got == base
    assert not eng.pool.swap_manifest and not eng._storm_pages
    _assert_accounted(eng)


def _chaos_disagg_case(setup, seed, fault_seed):
    cfg, api, params = setup
    rng = np.random.RandomState(seed)
    wl = _workload(rng, cfg.vocab_size, int(rng.randint(4, 8)))
    base = _run_disagg(_disagg(api, params), wl)
    orch = _disagg(api, params, faults=FaultConfig.chaos(
        seed=fault_seed, drop_handoff=0.3, delay_handoff=0.25))
    got = _run_disagg(orch, wl)
    assert got == base
    eng = orch.decode
    inj, rec = eng.faults.snapshot(), eng.recovery
    assert inj["corrupt_transfer"] + inj["truncate_transfer"] \
        == rec["unseal_fallback_transfer"]
    assert not orch._in_flight


@pytest.mark.parametrize("seed,fault_seed,num_pages,death",
                         [(0, 1, 12, True), (7, 3, 11, False),
                          (21, 9, 16, True)])
def test_chaos_schedule_seeded_paged(setup, seed, fault_seed, num_pages,
                                     death):
    """Fixed-seed slice of the chaos property — always runs, even where
    hypothesis is not installed."""
    _chaos_paged_case(setup, seed, fault_seed, num_pages, death)


@pytest.mark.parametrize("seed,fault_seed", [(2, 5), (13, 17)])
def test_chaos_schedule_seeded_disagg(setup, seed, fault_seed):
    _chaos_disagg_case(setup, seed, fault_seed)


def test_chaos_schedule_property_paged_local(setup):
    pytest.importorskip("hypothesis", reason="hypothesis not installed")
    from hypothesis import HealthCheck, given, settings, strategies as st

    @settings(deadline=None, max_examples=5, print_blob=True,
              suppress_health_check=list(HealthCheck))
    @given(seed=st.integers(0, 2**16 - 1),
           fault_seed=st.integers(0, 2**16 - 1),
           num_pages=st.sampled_from([11, 12, 16]),
           death=st.booleans())
    def prop(seed, fault_seed, num_pages, death):
        _chaos_paged_case(setup, seed, fault_seed, num_pages, death)

    prop()


def test_chaos_schedule_property_disagg(setup):
    pytest.importorskip("hypothesis", reason="hypothesis not installed")
    from hypothesis import HealthCheck, given, settings, strategies as st

    @settings(deadline=None, max_examples=3, print_blob=True,
              suppress_health_check=list(HealthCheck))
    @given(seed=st.integers(0, 2**16 - 1),
           fault_seed=st.integers(0, 2**16 - 1))
    def prop(seed, fault_seed):
        _chaos_disagg_case(setup, seed, fault_seed)

    prop()


# ---------------------------------------------------------------------------
# AOT: recovery performs zero post-warmup compilations
# ---------------------------------------------------------------------------
def test_warmed_chaos_zero_compiles(setup):
    """The acceptance gate: a warmed engine under a chaotic schedule —
    tampered swaps (recompute fallbacks), storms (preemptions + swap-ins),
    stalls (replans) — performs ZERO new XLA compilations; streams match
    the warmed fault-free run."""
    cfg, api, params = setup
    rng = np.random.RandomState(5)
    wl = _workload(rng, cfg.vocab_size, 8, lo=8, hi=17)
    base = _drive_checked(
        _engine(api, params, num_pages=12, warmup=True), wl)
    eng = _engine(api, params, num_pages=12, warmup=True,
                  faults=FaultConfig.chaos(seed=13, corrupt_swap=0.5,
                                           pool_storm=0.2,
                                           device_death=0.3))
    got = _drive_checked(eng, wl)
    assert got == base
    st = eng.stats()
    assert st["warmed"]
    assert st["compile_stalls"] == [], st["compile_stalls"]
    assert st["post_warmup_compiles"] == 0, st["post_warmup_compiles"]
    _assert_accounted(eng)


# ---------------------------------------------------------------------------
# Pipelined backend: device death on a real staged mesh (subprocess)
# ---------------------------------------------------------------------------
def test_pipelined_device_death_streams_identical(subproc):
    """Device death on the pipelined backend: stage-hosting domain dies
    mid-decode, active slots spill through the staged sealed gather, the
    placement re-solves around the corpse, and every stream matches the
    undisturbed pipelined run."""
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        import repro.models.layers as L
        L.DEFAULT_DTYPE = jnp.float32
        from repro.configs import get_arch, reduced
        from repro.launch.mesh import make_mesh
        from repro.models.api import build_model
        from repro.serving import EngineConfig, FaultConfig, ServingEngine
        from repro.serving.scheduler import DONE

        cfg = reduced(get_arch("llama3.2-1b"))
        api = build_model(cfg, max_seq=96)
        params = jax.tree.map(
            lambda x: x.astype(jnp.float32)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            api.init(jax.random.PRNGKey(0)))
        mesh = make_mesh((2, 2), ("pod", "data"))
        rng = np.random.RandomState(7)
        wl = [(rng.randint(1, cfg.vocab_size, size=4).tolist(), 10)
              for _ in range(4)]

        def drive(faults):
            ec = EngineConfig(num_slots=2, num_stages=2,
                              num_microbatches=2, max_seq=96,
                              prompt_capacity=8, request_capacity=20,
                              seal_boundary=False, page_size=4,
                              page_policy="demand", preempt_policy="swap",
                              telemetry_interval=4, allow_swap=False,
                              faults=faults)
            eng = ServingEngine(api, mesh=mesh, config=ec, params=params,
                                backend="pipelined")
            reqs, k = [], 0
            while k < len(wl) or eng.scheduler.has_work():
                if k < len(wl):
                    reqs.append(eng.submit(*wl[k])); k += 1
                eng.step()
                eng.check_page_invariants()
                assert eng.steps < 400
            assert all(r.status == DONE for r in reqs)
            return eng, [r.generated for r in reqs]

        _, base = drive(None)
        eng, got = drive(FaultConfig(seed=3, device_death=1.0,
                                     max_device_deaths=1))
        assert got == base, (got, base)
        st = eng.stats()
        assert st["faults"]["device_death"] == 1, st["faults"]
        assert st["recovery"]["device_loss_replans"] == 1, st["recovery"]
        assert st["failure_replans"] == 1
        assert len(st["excluded_devices"]) == 1
        print("PIPELINED-DEATH OK", st["recovery"])
    """, devices=4)
