"""AOT warmup: zero XLA compilations during steady-state serving.

The contract under test (DESIGN.md §AOT warmup & chunked prefill): after
``ServingEngine.warmup()`` returns, serving arbitrary traffic performs ZERO
new XLA compilations — asserted against the runtime via ``CompileMonitor``
(a ``jax.monitoring`` listener on the backend-compile event), not
inferred from engine bookkeeping.  Warmup must also be semantically inert:
the warm traffic pass is fully reset, so a warmed engine emits streams
identical to a cold one.

The monitor is process-global, so every test here drives exactly one
engine after its freeze point, never two concurrently.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.serving.scheduler import DONE
from repro.serving import MONITOR, AotRegistry
from repro.serving.aot import _sig_of


@pytest.fixture(scope="module")
def f32():
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


def _drive(eng, workload):
    reqs, k, gap = [], 0, 0
    while k < len(workload) or eng.scheduler.has_work():
        if k < len(workload) and gap <= 0:
            prompt, max_new, eos, gap = workload[k]
            reqs.append(eng.submit(prompt, max_new, eos_id=eos))
            k += 1
        gap -= 1
        eng.step()
        assert eng.steps < 1200, "schedule failed to drain"
    return reqs


def _workload(seed, n_req, vocab, prompt_cap):
    """Churn: every prefill bucket is hit, some requests finish early via
    eos, slots and pages recycle many times over."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n_req):
        n = 1 + (i % prompt_cap)         # sweep every prompt length
        prompt = rng.randint(0, vocab, size=n).tolist()
        eos = int(rng.randint(0, vocab)) if rng.rand() < 0.5 else None
        out.append((prompt, int(rng.randint(1, 9)), eos,
                    int(rng.randint(0, 3))))
    return out


def _assert_zero_post_warmup(eng):
    st = eng.stats()
    assert st["warmed"] and st["warmup_s"] > 0
    assert st["compile_stalls"] == [], st["compile_stalls"]
    assert st["post_warmup_compiles"] == 0, st["post_warmup_compiles"]


# ---------------------------------------------------------------------------
# CompileMonitor + AotFn unit behavior
# ---------------------------------------------------------------------------
def test_monitor_counts_real_compiles():
    MONITOR.install()
    before = MONITOR.backend_compiles
    # a never-before-seen closure forces a true XLA compilation
    salt = np.float32(before)
    fresh = jax.jit(lambda x: x * 3.0 + salt)
    fresh(jnp.zeros((before % 7 + 2,), jnp.float32))
    assert MONITOR.backend_compiles > before


def test_sig_of_discriminates_shapes_dtypes_and_scalars():
    a = jnp.zeros((2, 3), jnp.float32)
    assert _sig_of((a,)) == _sig_of((jnp.ones((2, 3), jnp.float32),))
    assert _sig_of((a,)) != _sig_of((jnp.zeros((3, 2), jnp.float32),))
    assert _sig_of((a,)) != _sig_of((jnp.zeros((2, 3), jnp.int32),))
    # python scalars hash as weak-typed by type name, not value
    assert _sig_of((a, 1)) == _sig_of((a, 2))
    assert _sig_of((a, 1)) != _sig_of((a, 1.0))
    # tree structure participates
    assert _sig_of(((a, a),)) != _sig_of((a, a))


def test_aotfn_warm_then_call_no_stall():
    reg = AotRegistry()
    f = reg.wrap("double", lambda x: x * 2)
    x4 = jnp.arange(4, dtype=jnp.float32)
    f.warm(x4)
    assert len(f.signatures) == 1
    reg.freeze()
    np.testing.assert_allclose(f(x4 + 1), (x4 + 1) * 2)
    assert reg.post_freeze_stalls == []
    # a signature never warmed is a recorded post-freeze stall, but the
    # call still succeeds (compile-and-cache, then serve)
    x8 = jnp.arange(8, dtype=jnp.float32)
    np.testing.assert_allclose(f(x8), x8 * 2)
    assert len(reg.post_freeze_stalls) == 1
    assert "double" in reg.post_freeze_stalls[0].describe()
    # ... and only once: the stall signature is now cached
    f(x8)
    assert len(reg.post_freeze_stalls) == 1


def test_aotfn_prefreeze_miss_is_not_a_post_freeze_stall():
    reg = AotRegistry()
    f = reg.wrap("inc", lambda x: x + 1)
    f(jnp.zeros((3,), jnp.float32))      # cold call before freeze
    assert len(reg.stalls) == 1 and not reg.stalls[0].frozen
    reg.freeze()
    assert reg.post_freeze_stalls == []


# ---------------------------------------------------------------------------
# Engine-level: warmup then churn, zero compiles
# ---------------------------------------------------------------------------
def test_warmed_engine_serves_churn_with_zero_compiles(setup):
    cfg, api, params = setup
    eng = _engine(api, params, warmup=True, allow_swap=False)
    wl = _workload(23, 20, cfg.vocab_size, prompt_cap=16)
    reqs = _drive(eng, wl)
    assert all(r.status == DONE for r in reqs)
    _assert_zero_post_warmup(eng)
    assert eng.stats()["post_warmup_compiles"] == 0


def test_warmed_chunked_engine_zero_compiles(setup):
    """Chunked prefill adds its own jitted entry points (prefill_chunk,
    commit_slot) and chunk-only steps — all must be covered by warmup."""
    cfg, api, params = setup
    eng = _engine(api, params, warmup=True, prefill_chunk=4,
                  allow_swap=False)
    wl = _workload(29, 16, cfg.vocab_size, prompt_cap=16)
    reqs = _drive(eng, wl)
    assert all(r.status == DONE for r in reqs)
    assert eng.stats()["chunked_admissions"] > 0
    _assert_zero_post_warmup(eng)
    assert eng.stats()["post_warmup_compiles"] == 0


def test_warmed_timeline_engine_zero_compiles(setup):
    cfg, api, params = setup
    eng = _engine(api, params, warmup=True, kv_layout="timeline",
                  allow_swap=False, max_seq=256)
    wl = _workload(31, 6, cfg.vocab_size, prompt_cap=8)
    reqs = _drive(eng, wl)
    assert all(r.status == DONE for r in reqs)
    _assert_zero_post_warmup(eng)
    assert eng.stats()["post_warmup_compiles"] == 0


# ---------------------------------------------------------------------------
# Engine-level: warmup is semantically inert
# ---------------------------------------------------------------------------
def test_warmup_does_not_change_streams(setup):
    """The warm traffic pass decodes real tokens through the real submit/
    step path; _reset_state must erase every trace of it.  Cold engine runs
    FIRST so its compilations don't land in the warmed engine's post-freeze
    window (the monitor is process-global)."""
    cfg, api, params = setup
    wl = _workload(37, 12, cfg.vocab_size, prompt_cap=16)

    cold = _engine(api, params)
    want = [tuple(r.generated) for r in _drive(cold, wl)]

    warmed = _engine(api, params, warmup=True, allow_swap=False)
    got = [tuple(r.generated) for r in _drive(warmed, wl)]
    assert got == want
    st = warmed.stats()
    assert st["steps"] < 1200 and st["admissions"] == len(wl)
    _assert_zero_post_warmup(warmed)


def test_warmup_requires_fresh_engine(setup):
    cfg, api, params = setup
    eng = _engine(api, params)
    eng.submit([1, 2, 3], 2)
    eng.step()
    with pytest.raises(AssertionError):
        eng.warmup()
