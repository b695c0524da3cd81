"""Serving engine: scheduler invariants, telemetry-driven re-planning,
stage-layout cache migration, and request isolation under continuous
batching. The shard_map pipelined-backend paths run in subprocesses on
virtual devices (as test_pipeline_runtime.py does)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.serving.scheduler import DONE, SlotScheduler


# ---------------------------------------------------------------------------
# Scheduler (pure host-side)
# ---------------------------------------------------------------------------
def test_scheduler_admission_fifo_and_slot_recycling():
    s = SlotScheduler(2)
    reqs = [s.submit([1, 2], max_new_tokens=2) for _ in range(5)]
    a = s.admit_next()
    b = s.admit_next()
    assert a[1].rid == 0 and b[1].rid == 1      # FIFO
    assert s.admit_next() is None               # no free slot
    s.check_invariants()
    # finish the first request -> its slot is immediately reusable
    assert s.on_token(a[0], 7) is None
    fin = s.on_token(a[0], 8)
    assert fin is reqs[0] and fin.status == DONE
    assert fin.generated == [7, 8]
    c = s.admit_next()
    assert c is not None and c[0] == a[0] and c[1].rid == 2
    s.check_invariants()


def test_scheduler_eos_completion_and_stats():
    s = SlotScheduler(1)
    s.submit([5], max_new_tokens=10, eos_id=99)
    slot, req = s.admit_next()
    s.on_token(slot, 1)
    fin = s.on_token(slot, 99)
    assert fin is req and fin.finished_by == "eos"
    assert s.free_slots == 1 and not s.has_work()
    st = s.stats()
    assert st["completed"] == 1 and st["tokens_out"] == 2


def test_scheduler_drain_randomized_invariants():
    rng = np.random.RandomState(0)
    s = SlotScheduler(3)
    for _ in range(17):
        s.submit([1], max_new_tokens=int(rng.randint(1, 5)))
    steps = 0
    while s.has_work():
        while s.admit_next() is not None:
            pass
        for slot, _req in list(s.active()):
            s.on_token(slot, int(rng.randint(0, 100)))
        s.check_invariants()
        steps += 1
        assert steps < 200
    assert len(s.finished) == 17
    assert sorted(r.rid for r in s.finished) == list(range(17))


# ---------------------------------------------------------------------------
# Telemetry -> replanner (no decode needed)
# ---------------------------------------------------------------------------
def _mini_replanner(num_stages=2):
    from repro.core.planner import profiles_from_arch
    from repro.enclave.domain import two_enclave_manager
    from repro.runtime.ft import OnlineReplanner
    cfg = reduced(get_arch("llama3.2-1b"))
    rm = two_enclave_manager()
    profs = profiles_from_arch(cfg, seq_len=1)
    rp = OnlineReplanner(rm, profs, n=1000, delta=0.9,
                         min_stages=num_stages)
    rp.plan()
    return rm, rp


def test_telemetry_straggler_triggers_replan():
    from repro.serving.telemetry import StageTelemetry
    rm, rp = _mini_replanner()
    assert len(rp.current.placement.stages) == 2   # min_stages honored
    tele = StageTelemetry(rp, interval=2)
    tele.inject(1, 10.0)
    # wall measurements proportional to prediction (healthy but for inject)
    shares = tele.predicted_shares()
    for step in (1, 2):
        tele.record_stage_times([0.01 * s for s in shares])
        ev = tele.maybe_observe(step)
    assert ev is not None and rp.replans == 1
    # exactly the straggler's device got derated
    derated = [d for d in rm.domains() if d.derate_factor < 1.0]
    assert len(derated) == 1


def test_telemetry_uniform_slowdown_no_replan():
    from repro.serving.telemetry import StageTelemetry
    rm, rp = _mini_replanner()
    tele = StageTelemetry(rp, interval=2)
    shares = tele.predicted_shares()
    for step in (1, 2, 3, 4):
        tele.record_stage_times([5.0 * s for s in shares])  # all 5x slow
        ev = tele.maybe_observe(step)
        assert ev is None
    assert rp.replans == 0


# ---------------------------------------------------------------------------
# Stage-layout cache migration (pure gather; no shard_map required)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("old,new", [((1, 3), (3, 1)), ((2, 2), (1, 3)),
                                     ((1, 1, 2), (2, 1, 1))])
def test_restage_cache_matches_direct_staging(old, new):
    from repro.models.api import build_model
    from repro.runtime.pipeline import PipelinedDecoder
    cfg = reduced(get_arch("llama3.2-1b"))
    api = build_model(cfg, max_seq=16)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("pod",))
    cache = api.init_cache(2, 16)
    seg = api.model.segments[0].name
    cache[seg] = jax.tree.map(
        lambda a: jnp.arange(a.size).reshape(a.shape).astype(a.dtype),
        cache[seg])
    S = len(old)
    d_old = PipelinedDecoder(api, mesh, num_stages=S, num_microbatches=1,
                             stage_blocks=old)
    d_new = PipelinedDecoder(api, mesh, num_stages=S, num_microbatches=1,
                             stage_blocks=new)
    migrated = d_old.restage_cache(d_old.stage_cache(cache), d_new)
    direct = d_new.stage_cache(cache)
    for a, b in zip(jax.tree.leaves(migrated[0]), jax.tree.leaves(direct[0])):
        assert jnp.array_equal(a, b)
    back = d_new.unstage_cache(migrated[0], migrated[1])
    for a, b in zip(jax.tree.leaves(back[seg]), jax.tree.leaves(cache[seg])):
        assert jnp.array_equal(a, b)


# ---------------------------------------------------------------------------
# Engine end-to-end (local backend; in-process)
# ---------------------------------------------------------------------------
@pytest.fixture
def f32_dtype():
    """Exact token comparisons need f32 end to end (params AND caches)."""
    import repro.models.layers as L
    old = L.DEFAULT_DTYPE
    L.DEFAULT_DTYPE = jnp.float32
    yield
    L.DEFAULT_DTYPE = old


def _f32_engine(arch="llama3.2-1b", **overrides):
    from repro.models.api import build_model
    from repro.serving import EngineConfig, ServingEngine
    cfg = reduced(get_arch(arch))
    api = build_model(cfg, max_seq=128)
    params = jax.tree.map(
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x,
        api.init(jax.random.PRNGKey(0)))
    kw = dict(num_slots=4, num_microbatches=2, max_seq=128,
              prompt_capacity=16, telemetry_interval=4,
              seal_boundary=False)
    kw.update(overrides)
    eng = ServingEngine(api, config=EngineConfig(**kw), params=params,
                        backend="local")
    return cfg, api, params, eng


def test_engine_request_isolation_matches_standalone(f32_dtype):
    """A request's token stream must not depend on when it was admitted or
    what shared the batch (offset prefill + per-slot start mask)."""
    cfg, api, params, eng = _f32_engine()
    rng = np.random.RandomState(0)
    cases = []
    for i in range(5):
        prompt = rng.randint(0, cfg.vocab_size,
                             size=int(rng.randint(3, 9))).tolist()
        cases.append((prompt, eng.submit(prompt, max_new_tokens=5 + i % 3)))
    eng.run(max_steps=100)
    eng.scheduler.check_invariants()
    assert all(r.status == DONE for _, r in cases)

    dec = jax.jit(api.decode_fn)
    for prompt, req in cases:
        cache = api.init_cache(1, 128)
        logits = None
        for t in prompt:
            logits, cache = dec(params, cache,
                                {"tokens": jnp.full((1, 1), t, jnp.int32)})
        toks = [int(jnp.argmax(logits[0]))]
        for _ in range(len(req.generated) - 1):
            logits, cache = dec(params, cache,
                                {"tokens": jnp.full((1, 1), toks[-1],
                                                    jnp.int32)})
            toks.append(int(jnp.argmax(logits[0])))
        assert toks == req.generated, (req.rid, toks, req.generated)


def test_engine_live_replan_token_streams_unchanged(f32_dtype):
    """Injected straggler -> replan -> boundary swap; the decoded streams
    must equal a run that never re-planned."""
    def run(inject):
        cfg, _, _, eng = _f32_engine()
        if inject:
            eng.telemetry.inject(1, 10.0)
        rng = np.random.RandomState(1)
        reqs = [eng.submit(rng.randint(0, cfg.vocab_size, size=6).tolist(),
                           12) for _ in range(4)]
        eng.run(max_steps=100)
        return eng, reqs

    e1, r1 = run(True)
    e2, r2 = run(False)
    assert e1.replanner.replans >= 1 and e1.swaps >= 1
    assert e1.stage_blocks != e2.stage_blocks
    assert e2.swaps == 0
    for a, b in zip(r1, r2):
        assert a.generated == b.generated


# ---------------------------------------------------------------------------
# Sampling (ROADMAP (g)): temperature / top-k, per-request PRNG threading
# ---------------------------------------------------------------------------
def test_sampler_temperature_zero_is_argmax():
    from repro.serving.sampling import TokenSampler
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(4, 32).astype(np.float32))
    s = TokenSampler(temperature=0.0)
    assert s.greedy
    got = s.sample(logits, np.arange(4), np.zeros(4, np.int64))
    assert got.tolist() == jnp.argmax(logits, -1).tolist()


def test_sampler_key_threading_is_slot_independent():
    """A request's sample depends only on (seed, rid, token index) — not on
    which batch row it occupies or what shares the batch."""
    from repro.serving.sampling import TokenSampler
    rng = np.random.RandomState(1)
    row = rng.randn(1, 64).astype(np.float32)
    s = TokenSampler(temperature=0.9, top_k=0, seed=7)
    alone = s.sample(jnp.asarray(row), np.asarray([5]), np.asarray([3]))[0]
    batch = np.repeat(rng.randn(3, 64).astype(np.float32), 1, 0)
    batch[1] = row[0]
    batched = s.sample(jnp.asarray(batch), np.asarray([0, 5, 9]),
                       np.asarray([0, 3, 0]))[1]
    assert alone == batched
    # a different rid (or position) re-keys the draw
    other = s.sample(jnp.asarray(row), np.asarray([6]), np.asarray([3]))[0]
    again = s.sample(jnp.asarray(row), np.asarray([5]), np.asarray([3]))[0]
    assert again == alone
    assert isinstance(int(other), int)      # may or may not differ; no crash


def test_sampler_top_k_restricts_support():
    from repro.serving.sampling import TokenSampler
    rng = np.random.RandomState(2)
    logits = jnp.asarray(rng.randn(8, 50).astype(np.float32))
    top2 = set(np.asarray(jnp.argsort(logits, -1)[:, -2:]).reshape(-1).tolist())
    s = TokenSampler(temperature=5.0, top_k=2, seed=0)
    for idx in range(50):
        got = s.sample(logits, np.arange(8), np.full(8, idx, np.int64))
        for b in range(8):
            row_top2 = np.asarray(jnp.argsort(logits[b])[-2:]).tolist()
            assert int(got[b]) in row_top2, (b, idx, got[b], row_top2)


def test_engine_sampling_temp_zero_token_equal_to_greedy(f32_dtype):
    """EngineConfig(temperature=0) must be token-identical to the default
    greedy engine."""
    def run(**kw):
        cfg, _, _, eng = _f32_engine(**kw)
        rng = np.random.RandomState(5)
        reqs = [eng.submit(rng.randint(0, cfg.vocab_size, size=5).tolist(), 6)
                for _ in range(3)]
        eng.run(max_steps=60)
        return [r.generated for r in reqs]

    assert run() == run(temperature=0.0, top_k=4)


def test_engine_sampling_deterministic_and_isolated(f32_dtype):
    """temperature > 0: re-running the same workload reproduces the streams
    (seeded), and a request sampled alone equals the same request sampled in
    a shared batch (per-request key threading)."""
    def run(n_extra):
        cfg, _, _, eng = _f32_engine(temperature=0.8, sample_seed=11)
        rng = np.random.RandomState(6)
        first = eng.submit(rng.randint(0, cfg.vocab_size, size=6).tolist(), 8)
        extra = [eng.submit(rng.randint(0, cfg.vocab_size,
                                        size=4).tolist(), 5)
                 for _ in range(n_extra)]
        eng.run(max_steps=80)
        assert first.status == DONE
        return first.generated

    batched = run(3)
    assert batched == run(3)                # seeded determinism
    assert batched == run(0)                # batch-mate independence


def test_engine_timeline_horizon_backpressure(f32_dtype):
    """The legacy timeline no longer crashes at the horizon: admission
    back-pressures (a request whose worst case can't fit waits), and a
    permanently blocked head of queue stalls the engine gracefully."""
    cfg, _, _, eng = _f32_engine(max_seq=32, prompt_capacity=8,
                                 kv_layout="timeline")
    fits = eng.submit([1, 2, 3], max_new_tokens=4)
    never = eng.submit([1, 2, 3], max_new_tokens=1000)   # > horizon forever
    eng.run(max_steps=100)
    assert fits.status == DONE
    assert never.status == "queued"
    assert eng.stalled
    assert any(e.kind == "backpressure" and e.detail["waiting_on"] ==
               "timeline" for e in eng.events)
    # repeated steps stay graceful (no RuntimeError) and make no progress
    before = eng.steps
    eng.step()
    assert eng.steps == before and eng.stalled


def test_engine_paged_submit_capacity_guard(f32_dtype):
    """Paged submissions exceeding per-request page capacity are rejected at
    submit time (the pool reserves worst-case pages at admission)."""
    cfg, _, _, eng = _f32_engine(max_seq=32, prompt_capacity=8)
    assert eng.kv_layout == "paged"
    with pytest.raises(AssertionError, match="request_capacity"):
        eng.submit([1, 2, 3], max_new_tokens=1000)


# ---------------------------------------------------------------------------
# HLO calibration hook (ROADMAP (d))
# ---------------------------------------------------------------------------
def test_profiles_calibrate_from_hlo():
    from repro.core.planner import profiles_from_arch
    cfg = reduced(get_arch("llama3.2-1b"))
    base = profiles_from_arch(cfg, seq_len=1)
    assert all(p.eff == 1.0 for p in base)
    # fallback: flag set but no artifact -> constants
    fb = profiles_from_arch(cfg, seq_len=1, calibrate_from_hlo=True)
    assert [p.eff for p in fb] == [p.eff for p in base]

    from repro.models.api import build_model
    api = build_model(cfg, max_seq=16)
    params = api.abstract_params()
    cache, _ = api.init_cache_specs(4, 16)
    compiled = jax.jit(api.decode_fn).lower(
        params, cache, {"tokens": jax.ShapeDtypeStruct((4, 1), jnp.int32)}
    ).compile()
    from repro.core.planner.profiling import hlo_calibration
    calib = hlo_calibration(cfg, 1, compiled, compiled_batch=4)
    assert calib is not None
    eff_c, act_c = calib
    assert 0.05 <= eff_c <= 1.0 and 0.1 <= act_c <= 100.0
    # the artifact's batch must be divided out: a batch-1 reading of the
    # same batch-4 executable reports ~4x the per-sequence work
    eff_1, act_1 = hlo_calibration(cfg, 1, compiled, compiled_batch=1)
    assert act_1 == pytest.approx(4 * act_c)

    cal = profiles_from_arch(cfg, seq_len=1, calibrate_from_hlo=True,
                             compiled=compiled, compiled_batch=4)
    assert {p.eff for p in cal} == {eff_c}
    # activation traffic rescaled uniformly by the measured bytes ratio
    ratios = {round(p.act_bytes / b.act_bytes, 9)
              for p, b in zip(cal, base)}
    assert ratios == {round(act_c, 9)}


# ---------------------------------------------------------------------------
# Pipelined backend (subprocess on virtual devices)
# ---------------------------------------------------------------------------
ENGINE_PIPE_CODE = """
import jax, jax.numpy as jnp, numpy as np
import repro.models.layers as L
L.DEFAULT_DTYPE = jnp.float32
from repro.configs import get_arch, reduced
from repro.models.api import build_model
from repro.launch.mesh import make_mesh
from repro.serving import EngineConfig, ServingEngine

cfg = reduced(get_arch('llama3.2-1b'))
api = build_model(cfg, max_seq=128)
params = jax.tree.map(lambda x: x.astype(jnp.float32)
                      if jnp.issubdtype(x.dtype, jnp.floating) else x,
                      api.init(jax.random.PRNGKey(0)))
mesh = make_mesh((2, 2), ('pod', 'data'))

def run(backend, inject, kv_layout='paged'):
    ec = EngineConfig(num_slots=4, num_microbatches=2, max_seq=128,
                      prompt_capacity=16, telemetry_interval=4,
                      seal_boundary=False, kv_layout=kv_layout)
    eng = ServingEngine(api, mesh=mesh, config=ec, params=params,
                        backend=backend)
    if inject:
        eng.telemetry.inject(1, 25.0)
    rng = np.random.RandomState(3)
    reqs = [eng.submit(rng.randint(0, cfg.vocab_size,
                                   size=int(rng.randint(3, 9))).tolist(),
                       10) for _ in range(5)]
    eng.run(max_steps=120)
    assert all(r.status == 'done' for r in reqs), [r.status for r in reqs]
    return eng, [r.generated for r in reqs]

{body}
"""


def test_engine_pipelined_matches_local(subproc):
    body = """
e_pipe, toks_pipe = run('pipelined', inject=False)
assert e_pipe.backend_kind == 'pipelined' and e_pipe.kv_layout == 'paged'
e_loc, toks_loc = run('local', inject=False)
assert toks_pipe == toks_loc, (toks_pipe, toks_loc)
e_tl, toks_tl = run('pipelined', inject=False, kv_layout='timeline')
assert e_tl.kv_layout == 'timeline'
assert toks_tl == toks_loc, (toks_tl, toks_loc)
print('OK')
"""
    out = subproc(ENGINE_PIPE_CODE.format(body=body), devices=4)
    assert "OK" in out


def test_engine_pipelined_live_swap_token_exact(subproc):
    """The acceptance demo in-test: straggler -> re-plan -> restage_cache
    migration; streams identical to an un-swapped pipelined run."""
    body = """
e1, toks1 = run('pipelined', inject=True)
assert e1.swaps >= 1, [e.kind for e in e1.events]
assert any(e.kind == 'swap' and e.detail['migrated'] for e in e1.events)
e2, toks2 = run('pipelined', inject=False)
assert e1.stage_blocks != e2.stage_blocks, (e1.stage_blocks, e2.stage_blocks)
assert toks1 == toks2, (toks1, toks2)
print('OK')
"""
    out = subproc(ENGINE_PIPE_CODE.format(body=body), devices=4, timeout=1200)
    assert "OK" in out
