"""Paged KV cache: engine-level correctness.

The contract under test (DESIGN.md §Paged KV cache): the paged engine is a
drop-in replacement for the legacy shared-timeline engine — token streams
identical over arbitrary admission/completion/recycling schedules — while
lifting the ``max_seq`` lifetime bound (slots and pages recycle forever) and
admitting whole prompts in one jitted prefill call.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.serving.scheduler import DONE, PagePool


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
              prompt_capacity=16, telemetry_interval=4, seal_boundary=False,
              page_size=4)
    kw.update(overrides)
    return ServingEngine(api, config=EngineConfig(**kw), params=params,
                         backend="local")


# ---------------------------------------------------------------------------
# PagePool allocator
# ---------------------------------------------------------------------------
def test_page_pool_reserves_and_recycles():
    p = PagePool(num_pages=9, page_size=4)
    assert p.free_pages == 8 and p.pages_needed(9) == 3
    a = p.alloc(5)
    b = p.alloc(3)
    assert a is not None and b is not None and p.free_pages == 0
    assert 0 not in a + b and len(set(a + b)) == 8
    assert p.alloc(1) is None            # exhausted -> caller waits
    p.release(a)
    assert p.free_pages == 5 and p.peak_in_use == 8
    c = p.alloc(5)
    assert sorted(c) == sorted(a)        # recycled pages are reused


# ---------------------------------------------------------------------------
# Property: paged engine == legacy timeline engine, randomized schedules
# ---------------------------------------------------------------------------
def _workload(seed, n_req, vocab, prompt_cap):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n_req):
        prompt = rng.randint(0, vocab,
                             size=int(rng.randint(2, prompt_cap))).tolist()
        max_new = int(rng.randint(1, 9))
        # an in-vocab eos sometimes fires early -> random completion order
        eos = int(rng.randint(0, vocab)) if rng.rand() < 0.5 else None
        out.append((prompt, max_new, eos, int(rng.randint(0, 3))))
    return out


def _assert_drained(eng):
    """After a full drain no slot holds pages; every non-free page is an
    index-retained prefix page (the COW prefix cache deliberately outlives
    requests). Under reserve policy the index is empty, so this reduces to
    the old 'everything recycled' check."""
    assert not eng.slot_pages
    eng.check_page_invariants()
    st = eng.stats()
    retained = len(eng.pool.prefix_index)
    assert st["free_pages"] + retained == st["num_pages"] - 1, \
        (st["free_pages"], retained, st["num_pages"])


def _drive(eng, workload, restage_at=None, restage_fn=None):
    """Submit with randomized inter-arrival gaps; step to drain. Optionally
    invoke ``restage_fn(eng)`` once after ``restage_at`` engine steps."""
    reqs, k, gap, restaged = [], 0, 0, False
    while k < len(workload) or eng.scheduler.has_work():
        if k < len(workload) and gap <= 0:
            prompt, max_new, eos, gap = workload[k]
            reqs.append(eng.submit(prompt, max_new, eos_id=eos))
            k += 1
        gap -= 1
        eng.step()
        if restage_at is not None and not restaged \
                and eng.steps >= restage_at:
            restage_fn(eng)
            restaged = True
        assert eng.steps < 600, "schedule failed to drain"
    return reqs


def test_paged_token_equal_to_timeline_randomized(setup):
    """Randomized admission/completion/recycling schedules: every request's
    stream must be identical across (timeline, paged per-token-prefill,
    paged batched-prefill), including under page back-pressure (a pool too
    small to hold every slot forces admissions to wait on recycling)."""
    cfg, api, params = setup
    for seed in (0, 1):
        wl = _workload(seed, 10, cfg.vocab_size, 12)
        streams = {}
        for name, kw in (
                ("timeline", dict(kv_layout="timeline")),
                ("paged", dict()),
                ("paged_pertoken", dict(batched_prefill=False)),
                # 3 slots' worth of pages for 4 slots: forced back-pressure
                ("paged_tight", dict(num_pages=19, request_capacity=24)),
        ):
            eng = _engine(api, params, **kw)
            reqs = _drive(eng, wl)
            assert all(r.status == DONE for r in reqs), (name, seed)
            eng.scheduler.check_invariants()
            streams[name] = [r.generated for r in reqs]
            if name.startswith("paged"):
                _assert_drained(eng)
        base = streams.pop("timeline")
        for name, got in streams.items():
            assert got == base, (seed, name)


def test_paged_tight_pool_backpressures_admission(setup):
    """Under worst-case reservation a pool sized for one request at a time
    serializes admissions through page recycling instead of crashing or
    deadlocking (demand policy would instead overlap them — covered by the
    property tests below)."""
    cfg, api, params = setup
    eng = _engine(api, params, num_slots=2, prompt_capacity=8,
                  request_capacity=12, num_pages=4,   # 3 usable = one request
                  page_policy="reserve")
    a = eng.submit([1, 2, 3], 4)
    b = eng.submit([4, 5, 6], 4)
    reqs = eng.run(max_steps=200)
    assert a.status == DONE and b.status == DONE
    assert b.admit_step >= a.finish_step          # waited on a's pages
    assert any(e.kind == "backpressure" and e.detail["waiting_on"] == "pages"
               for e in eng.events)
    assert not eng.stalled


# ---------------------------------------------------------------------------
# Lifetime: the engine outlives any timeline horizon
# ---------------------------------------------------------------------------
def test_paged_engine_outlives_timeline_horizon(setup):
    """Serve > max_seq total positions through recycled slots/pages — the
    legacy layout's hard lifetime bound. max_seq=32 here; the stream decodes
    far more shared-timeline-equivalent positions than that."""
    cfg, api, params = setup
    eng = _engine(api, params, num_slots=2, max_seq=32, prompt_capacity=8,
                  request_capacity=16)
    rng = np.random.RandomState(3)
    reqs = [eng.submit(rng.randint(0, cfg.vocab_size, size=5).tolist(), 7)
            for _ in range(12)]
    eng.run(max_steps=500)
    assert all(r.status == DONE for r in reqs)
    total_positions = sum(len(r.prompt) + len(r.generated) for r in reqs)
    assert total_positions > 2 * eng.config.max_seq    # 144 > 64
    assert eng.steps > eng.config.max_seq              # decode alone passes it
    _assert_drained(eng)                               # everything recycled
    # slot churn actually happened (2 slots, 12 requests)
    slots_used = {r.slot for r in reqs}
    assert slots_used == {0, 1}


# ---------------------------------------------------------------------------
# Batched prefill: one call, token streams identical to per-token
# ---------------------------------------------------------------------------
def test_batched_prefill_64_token_prompt_single_call(setup):
    """Acceptance: a 64-token prompt admits in ONE prefill call with a
    stream identical to per-token prefill admission."""
    cfg, api, params = setup

    def run(batched):
        eng = _engine(api, params, prompt_capacity=64, request_capacity=80,
                      batched_prefill=batched)
        rng = np.random.RandomState(4)
        req = eng.submit(rng.randint(0, cfg.vocab_size, size=64).tolist(), 6)
        eng.run(max_steps=50)
        assert req.status == DONE
        return eng, req.generated

    e1, toks1 = run(True)
    e2, toks2 = run(False)
    assert toks1 == toks2
    assert e1.prefill_calls == 1                  # whole prompt, one call
    assert e2.prefill_calls == 64                 # the seed-path baseline


def test_prefill_bucketing_bounds_compiles(setup):
    """Distinct prompt lengths share power-of-two buckets: admissions at
    lengths {3, 4} and {5, 7, 8} each reuse one padded prefill shape."""
    cfg, api, params = setup
    eng = _engine(api, params)
    assert eng._bucket(3) == eng._bucket(4) == 4
    assert eng._bucket(5) == eng._bucket(7) == eng._bucket(8) == 8
    assert eng._bucket(9) == 16
    assert eng._bucket(16) == 16


# ---------------------------------------------------------------------------
# Demand paging + COW + preemption vs the worst-case-reservation oracle
# ---------------------------------------------------------------------------
def _drive_checked(eng, wl, max_steps=800):
    """Submit with per-request arrival gaps; audit scheduler + page-pool
    invariants after EVERY step; drain and assert completion."""
    reqs, k, gap = [], 0, 0
    while k < len(wl) or eng.scheduler.has_work():
        if k < len(wl) and gap <= 0:
            prompt, max_new, eos, gap = wl[k]
            reqs.append(eng.submit(prompt, max_new, eos_id=eos))
            k += 1
        gap -= 1
        eng.step()
        eng.scheduler.check_invariants()
        eng.check_page_invariants()
        assert eng.steps < max_steps, "schedule failed to drain"
    assert all(r.status == DONE for r in reqs)
    _assert_drained(eng)
    return [r.generated for r in reqs]


def _assert_null_page_zero(eng, api):
    """Device-side invariant: page 0 is never written. Admission scatters
    and decode writes aimed at it are redirected to the out-of-range drop
    sentinel, so the pool's page 0 must still be all-zero."""
    seg = api.model.segments[0].name
    k_pool, v_pool = eng.backend.cache[seg]
    assert not np.asarray(k_pool[:, 0]).any()
    assert not np.asarray(v_pool[:, 0]).any()


def _shared_prefix_workload(rng, vocab, n_req, share_ratio):
    """Mixed prompts: `share_ratio` of them extend one of two common system
    prompts (COW prefix sharing), the rest are fully random."""
    sys_prompts = [rng.randint(0, vocab,
                               size=int(rng.randint(4, 11))).tolist()
                   for _ in range(2)]
    wl = []
    for _ in range(n_req):
        if rng.rand() < share_ratio:
            base = sys_prompts[int(rng.randint(2))]
            prompt = (base + rng.randint(
                0, vocab, size=int(rng.randint(1, 6))).tolist())[:16]
        else:
            prompt = rng.randint(0, vocab,
                                 size=int(rng.randint(2, 13))).tolist()
        eos = int(rng.randint(0, vocab)) if rng.rand() < 0.4 else None
        wl.append((prompt, int(rng.randint(1, 9)), eos,
                   int(rng.randint(0, 3))))
    return wl


def test_demand_paging_property_matches_reserve_oracle(setup):
    """THE tentpole property (hypothesis): over randomized admission / EOS /
    shared-prefix / tight-pool (preemption-inducing) schedules, the
    demand-paged + COW + preemption engine produces token streams
    bit-identical to the PR 5 worst-case-reservation engine, with PagePool
    refcount/partition invariants audited after every step and the null
    page provably unwritten on device."""
    hypothesis = pytest.importorskip(
        "hypothesis", reason="hypothesis not installed")
    from hypothesis import HealthCheck, given, settings, strategies as st
    cfg, api, params = setup

    @settings(deadline=None, max_examples=6, print_blob=True,
              suppress_health_check=list(HealthCheck))
    @given(seed=st.integers(0, 2**16 - 1),
           num_pages=st.sampled_from([8, 9, 11, 14]),
           share_ratio=st.sampled_from([0.0, 0.5, 0.9]))
    def prop(seed, num_pages, share_ratio):
        rng = np.random.RandomState(seed)
        wl = _shared_prefix_workload(rng, cfg.vocab_size,
                                     int(rng.randint(4, 10)), share_ratio)
        oracle_eng = _engine(api, params, request_capacity=24,
                             page_policy="reserve")
        oracle = _drive_checked(oracle_eng, wl)
        eng = _engine(api, params, request_capacity=24,
                      num_pages=num_pages, page_policy="demand")
        got = _drive_checked(eng, wl)
        assert got == oracle
        _assert_null_page_zero(eng, api)

    prop()


def test_preemption_resumes_token_exact(setup):
    """A pool too small for concurrent worst cases forces preemption:
    victims requeue with their generated tokens as a prompt extension and
    every stream still matches the roomy-pool oracle bit-for-bit."""
    cfg, api, params = setup
    rng = np.random.RandomState(1)
    wl = [(rng.randint(0, cfg.vocab_size, size=4).tolist(), 14, None, 0)
          for _ in range(6)]
    oracle = _drive_checked(_engine(api, params, request_capacity=24,
                                    page_policy="reserve"), wl)
    eng = _engine(api, params, num_slots=3, num_microbatches=1,
                  request_capacity=24, num_pages=8, page_policy="demand",
                  prefix_sharing=False)
    got = _drive_checked(eng, wl)
    assert got == oracle
    st = eng.stats()
    assert st["preemptions"] > 0          # the tight pool actually preempted
    assert any(r.preemptions > 0 for r in eng.scheduler.finished)
    assert any(e.kind == "preempt" for e in eng.events)
    _assert_null_page_zero(eng, api)


def test_cow_prefix_sharing_saves_pages_and_forks(setup):
    """Identical system prompts dedupe to one physical copy: admissions hit
    the prefix index (cow_hits), diverge by forking (forks), streams stay
    oracle-exact, and peak page use is strictly below the no-sharing run."""
    cfg, api, params = setup
    rng = np.random.RandomState(2)
    sys_prompt = rng.randint(0, cfg.vocab_size, size=12).tolist()
    wl = [(sys_prompt + rng.randint(0, cfg.vocab_size,
                                    size=3).tolist(), 5, None, 1)
          for _ in range(6)]
    oracle = _drive_checked(_engine(api, params, request_capacity=24,
                                    page_policy="reserve"), wl)

    def run(sharing):
        eng = _engine(api, params, request_capacity=24,
                      page_policy="demand", prefix_sharing=sharing)
        got = _drive_checked(eng, wl)
        assert got == oracle
        _assert_null_page_zero(eng, api)
        return eng.stats()

    shared, private = run(True), run(False)
    assert shared["cow_hits"] > 0 and shared["forks"] > 0
    assert private["cow_hits"] == 0 and private["forks"] == 0
    assert shared["peak_pages_in_use"] < private["peak_pages_in_use"]


def test_demand_admits_more_concurrent_slots_than_reserve(setup):
    """The capacity win the ISSUE demands: at a FIXED tight pool size,
    demand paging sustains strictly more concurrent slots than worst-case
    reservation (which serializes), with identical token streams."""
    cfg, api, params = setup
    rng = np.random.RandomState(3)
    wl = [(rng.randint(0, cfg.vocab_size, size=6).tolist(), 8, None, 0)
          for _ in range(6)]
    oracle = _drive_checked(_engine(api, params, request_capacity=24,
                                    page_policy="reserve"), wl)

    def run(policy):
        eng = _engine(api, params, request_capacity=24, num_pages=14,
                      page_policy=policy)
        got = _drive_checked(eng, wl)
        assert got == oracle
        return eng.stats()

    reserve, demand = run("reserve"), run("demand")
    assert demand["peak_running_slots"] > reserve["peak_running_slots"]
    assert demand["steps"] < reserve["steps"]   # overlap -> fewer steps


# ---------------------------------------------------------------------------
# Stage-layout migration of paged pools (restage_cache across a swap)
# ---------------------------------------------------------------------------
def test_paged_pool_restage_roundtrip_token_exact(setup):
    """Mid-schedule, migrate the live page pools old-boundaries -> new
    boundaries through PipelinedDecoder.restage_cache (the live-swap path)
    and keep decoding: streams must equal an undisturbed run. Covers the
    cache-migration math locally; the full shard_map swap runs in the CI
    pipelined tests."""
    from repro.runtime.pipeline import PipelinedDecoder
    cfg, api, params = setup
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("pod",))
    seg = api.model.segments[0].name

    def restage(eng):
        d_old = PipelinedDecoder(api, mesh, num_stages=2, num_microbatches=1,
                                 stage_blocks=(1, 3))
        d_new = PipelinedDecoder(api, mesh, num_stages=2, num_microbatches=1,
                                 stage_blocks=(3, 1))
        pool = eng.backend.cache[seg]
        staged = d_old._stage_tree(pool)
        migrated = d_old.restage_cache((staged,), d_new)
        eng.backend.cache[seg] = tuple(
            d_new.unstage_cache(migrated[0], 0)[seg])

    wl = _workload(5, 8, cfg.vocab_size, 12)
    e1 = _engine(api, params)
    r1 = _drive(e1, wl, restage_at=6, restage_fn=restage)
    e2 = _engine(api, params)
    r2 = _drive(e2, wl)
    assert all(r.status == DONE for r in r1 + r2)
    assert [r.generated for r in r1] == [r.generated for r in r2]


# ---------------------------------------------------------------------------
# Decode attention: which path the local backend binds, what it streams
# ---------------------------------------------------------------------------
def test_local_backend_binds_gather_off_tpu_and_pallas_on_request(setup):
    """Off a TPU the paged local backend decodes through the jnp page
    gather; ``use_kernel`` binds the (here interpreted) Pallas kernel."""
    cfg, api, params = setup
    assert jax.default_backend() == "cpu"
    plain = _engine(api, params, request_capacity=24)
    kernel = _engine(api, params, request_capacity=24, use_kernel=True)
    assert plain.stats()["decode_attn_path"] == "gather"
    assert kernel.stats()["decode_attn_path"] == "pallas"


def test_kernel_decode_token_exact_with_idle_slots_and_shared_prefix(setup):
    """The interpreted kernel serves the same streams as the page gather
    over several decode steps, with a slot always idle (3 requests, 4
    slots) and block tables that alias a COW-shared prefix."""
    cfg, api, params = setup
    rng = np.random.RandomState(5)
    sys_prompt = rng.randint(0, cfg.vocab_size, size=10).tolist()
    wl = [(sys_prompt + rng.randint(0, cfg.vocab_size,
                                    size=int(n)).tolist(), 6, None, 1)
          for n in (2, 5, 3)]
    oracle = _drive_checked(_engine(api, params, request_capacity=24,
                                    page_policy="demand"), wl)
    eng = _engine(api, params, request_capacity=24, page_policy="demand",
                  use_kernel=True)
    got = _drive_checked(eng, wl)
    assert got == oracle
    st = eng.stats()
    assert st["decode_attn_path"] == "pallas" and st["cow_hits"] > 0
    assert st["peak_running_slots"] < eng.config.num_slots
    _assert_null_page_zero(eng, api)


def test_decode_attention_page_counters(setup):
    """Prompts of 3, 8 and 5 tokens, three tokens each: the prefill gives
    the first, two decode calls the rest. At page size 4 the calls attend
    over 4, 9, 6 and then 5, 10, 7 tokens: 1 + 3 + 2 and 2 + 3 + 2 live
    pages; the tables span 4 slots x 6 pages each call."""
    cfg, api, params = setup
    eng = _engine(api, params, request_capacity=24)
    assert eng.pages_per_slot == 6
    rng = np.random.RandomState(6)
    for n in (3, 8, 5):
        eng.submit(rng.randint(0, cfg.vocab_size, size=n).tolist(), 3)
    while eng.scheduler.has_work():
        eng.step()
    st = eng.stats()
    assert eng.telemetry.steps_recorded == 2
    assert st["decode_attn_live_pages"] == 6 + 7
    assert st["decode_attn_table_pages"] == 2 * 4 * 6
