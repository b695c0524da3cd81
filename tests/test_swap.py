"""Two-tier paged KV: sealed host swap-out/swap-in on preemption.

The contract under test (DESIGN.md §Two-tier KV & swap): when the demand
pool runs dry, ``preempt_policy="swap"`` seals the victim's private pages
through the lossless bit-cipher into host buffers and restores them
bit-exactly on resume — no re-prefill, O(pages transferred) instead of
O(generated tokens) — with token streams identical to both the recompute
oracle (PR 6) and an undisturbed run.  COW-shared pages are never spilled:
the swap manifest pins them and swap-in re-adopts them in place.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.enclave import sealing
from repro.serving.scheduler import DONE, SWAPPED, PagePool


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


def _drive_checked(eng, wl, max_steps=900):
    """Submit with per-request arrival gaps; audit scheduler + page-pool +
    swap-manifest invariants after EVERY step; drain and assert done."""
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
    assert all(r.status == DONE for r in reqs)
    return [r.generated for r in reqs]


# ---------------------------------------------------------------------------
# Lossless bit-cipher (the sealing boundary of the host tier)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_seal_bits_roundtrip_bit_exact(dtype, use_kernel):
    from repro.kernels import ops as K
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(6, 96).astype(np.float32)).astype(dtype)
    key, ctr = jnp.uint32(0xBEEF), jnp.uint32(41)
    cipher = K.seal_bits(x, key, ctr, use_kernel=use_kernel)
    want_ct = jnp.uint32 if dtype == jnp.float32 else jnp.uint16
    assert cipher.dtype == want_ct
    back = K.unseal_bits(cipher, key, ctr, out_dtype=dtype,
                         use_kernel=use_kernel)
    # bit-exact, not allclose: the swap tier must restore KV identically
    assert np.array_equal(np.asarray(x, np.float32),
                          np.asarray(back, np.float32))
    # the cipher is not the plaintext, and a wrong counter doesn't decrypt
    assert not np.array_equal(
        np.asarray(cipher),
        np.asarray(jax.lax.bitcast_convert_type(x, cipher.dtype)))
    wrong = K.unseal_bits(cipher, key, ctr + 1, out_dtype=dtype,
                          use_kernel=use_kernel)
    assert not np.array_equal(np.asarray(wrong, np.float32),
                              np.asarray(x, np.float32))


def test_seal_bits_kernel_matches_ref_cipher():
    """Kernel and oracle produce the SAME ciphertext — either side can
    seal and the other unseal (pages sealed on-device, restored anywhere)."""
    from repro.kernels import ops as K
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(5, 64).astype(np.float32))
    key, ctr = jnp.uint32(3), jnp.uint32(9)
    ck = K.seal_bits(x, key, ctr, use_kernel=True)
    cr = K.seal_bits(x, key, ctr, use_kernel=False)
    assert np.array_equal(np.asarray(ck), np.asarray(cr))


def test_swap_counter_separates_planes():
    """K and V planes draw from disjoint keystreams, and distinct swap
    sequence numbers never reuse a keystream."""
    x = jnp.ones((2, 32), jnp.float32)
    key = jnp.uint32(5)
    ck = sealing.seal_pages(x, key, 0, part=0)
    cv = sealing.seal_pages(x, key, 0, part=1)
    assert not np.array_equal(np.asarray(ck), np.asarray(cv))
    c2 = sealing.seal_pages(x, key, 1, part=0)
    assert not np.array_equal(np.asarray(ck), np.asarray(c2))


# ---------------------------------------------------------------------------
# SwapManifest bookkeeping on the bare pool
# ---------------------------------------------------------------------------
def test_page_pool_swap_manifest_accounting():
    p = PagePool(num_pages=9, page_size=4)
    pages = p.alloc(4)
    a, b = pages[:2], pages[2:]
    # b's first page is COW-shared: frozen in the prefix index (+1 ref)
    skey = (1, 2, 3, 4)
    p.register_prefix(skey, b[0])
    payload = (np.zeros((2, 8), np.uint32), np.zeros((2, 8), np.uint32))
    p.swap_out(7, [("sealed", 0), ("sealed", 1)], payload, 8, counter=0)
    p.release(a)
    p.swap_out(8, [("shared", (skey, b[0])), ("sealed", 1)],
               payload, 8, counter=1)
    p.release(b)
    assert p.swapped_pages == 3          # sealed rows only, not pins
    assert p.stats() == {"swapped_pages": 3, "swap_outs": 2, "swap_ins": 0,
                     "pending_transfers": 0, "transfers_in": 0,
                     "transfer_demotions": 0}
    p.check_invariants({})               # pins vs free list vs index agree
    man = p.swap_in(7)
    assert man.n_tokens == 8 and man.sealed_pages == 2
    assert p.stats()["swap_ins"] == 1 and p.swapped_pages == 1
    # dropping the remaining manifest releases its shared pin
    rc = p.refcount[b[0]]
    p.drop_swap(8)
    assert p.refcount[b[0]] == rc - 1
    assert not p.swap_manifest
    p.check_invariants({})


def test_swap_out_rejects_unindexed_shared_page():
    """A "shared" manifest entry must reference a page frozen in the
    prefix index under that key — otherwise the pin could not guarantee
    re-adoption and swap_out refuses it."""
    p = PagePool(num_pages=5, page_size=4)
    (pg,) = p.alloc(1)
    with pytest.raises(AssertionError):
        p.swap_out(1, [("shared", ((9,), pg))], (None, None), 4, counter=0)


# ---------------------------------------------------------------------------
# Engine: swap preemption resumes without recompute, streams exact
# ---------------------------------------------------------------------------
def test_swap_preemption_resumes_token_exact(setup):
    """Tight pool forces preemption; the swap engine's streams must equal
    the roomy reserve oracle, resume without re-prefill, and drain the
    host tier completely."""
    cfg, api, params = setup
    rng = np.random.RandomState(1)
    wl = [(rng.randint(0, cfg.vocab_size, size=4).tolist(), 14, None, 0)
          for _ in range(6)]
    oracle = _drive_checked(_engine(api, params, request_capacity=24,
                                    page_policy="reserve"), wl)
    eng = _engine(api, params, num_slots=3, num_microbatches=1,
                  request_capacity=24, num_pages=8, page_policy="demand",
                  prefix_sharing=False, preempt_policy="swap")
    got = _drive_checked(eng, wl)
    assert got == oracle
    st = eng.stats()
    assert st["preempt_policy"] == "swap"
    assert st["swap_outs"] > 0 and st["swap_ins"] > 0
    assert st["swap_outs"] == st["swap_ins"] + st["swap_fallbacks"]
    assert st["swapped_pages"] == 0 and not eng.pool.swap_manifest
    # a swap resume is an admission WITHOUT a prefill: it arrives through
    # the dedicated restore path, tagged resumed="swap" on its admit event
    resumes = [e for e in eng.events if e.kind == "admit"
               and (e.detail or {}).get("resumed") == "swap"]
    assert len(resumes) == st["swap_ins"]


def test_swap_preemption_with_shared_prefix_pins(setup):
    """COW-shared pages are never spilled: the manifest pins them across
    the swap and re-adopts them on resume, streams still oracle-exact."""
    cfg, api, params = setup
    rng = np.random.RandomState(2)
    sysp = rng.randint(0, cfg.vocab_size, size=8).tolist()
    wl = [(sysp + rng.randint(0, cfg.vocab_size, size=3).tolist(),
           10, None, 0) for _ in range(6)]
    oracle = _drive_checked(_engine(api, params, request_capacity=24,
                                    page_policy="reserve"), wl)
    eng = _engine(api, params, num_slots=3, num_microbatches=1,
                  request_capacity=24, num_pages=11, page_policy="demand",
                  prefix_sharing=True, preempt_policy="swap")
    got = _drive_checked(eng, wl)
    assert got == oracle
    st = eng.stats()
    assert st["swap_outs"] > 0 and st["cow_hits"] > 0
    shared_pinned = [e for e in eng.events if e.kind == "preempt"
                     and (e.detail or {}).get("policy") == "swap"
                     and e.detail.get("shared_pages", 0) > 0]
    assert shared_pinned, "no preemption pinned a COW-shared page"


def test_swap_accounting_and_sealed_bytes_roundtrip(setup):
    """Swap-out frees device pages immediately (the host tier is not
    device pressure: free_pages rises, peak_demand does not move) and the
    sealed payload unseals bit-exactly to the pre-preemption pool pages."""
    cfg, api, params = setup
    eng = _engine(api, params, num_slots=2, request_capacity=24,
                  page_policy="demand", prefix_sharing=False,
                  preempt_policy="swap")
    rng = np.random.RandomState(3)
    req = eng.submit(rng.randint(0, cfg.vocab_size, size=8).tolist(), 8)
    while len(req.generated) < 4:
        eng.step()
    seg = api.model.segments[0].name
    k_pool, v_pool = eng.backend.cache[seg]
    pages = list(eng.slot_pages[req.slot])
    want_k = {pg: np.asarray(k_pool[:, pg]) for pg in pages}
    want_v = {pg: np.asarray(v_pool[:, pg]) for pg in pages}
    free0, peak0 = eng.pool.free_pages, eng.pool.peak_demand

    eng._preempt(req.slot, req)
    assert req.status == SWAPPED
    man = eng.pool.manifest(req.rid)
    assert man.sealed_pages == len(pages)      # no sharing: all private
    assert eng.pool.free_pages == free0 + len(pages)
    assert eng.pool.peak_demand == peak0       # host pages aren't demand
    eng.check_page_invariants()

    ck, cv = man.payload
    L_, KVH, Pg, D = (k_pool.shape[0],) + tuple(k_pool.shape[2:])
    plain_k = np.asarray(sealing.unseal_pages(
        jnp.asarray(ck), eng._key, jnp.uint32(man.counter),
        jnp.float32, part=0))
    plain_v = np.asarray(sealing.unseal_pages(
        jnp.asarray(cv), eng._key, jnp.uint32(man.counter),
        jnp.float32, part=1))
    for i, (tag, val) in enumerate(man.entries):
        assert tag == "sealed" and val == i
        pg = pages[i]
        assert np.array_equal(plain_k[i].reshape(L_, KVH, Pg, D),
                              want_k[pg])
        assert np.array_equal(plain_v[i].reshape(L_, KVH, Pg, D),
                              want_v[pg])

    while eng.scheduler.has_work():
        eng.step()
    assert req.status == DONE
    assert eng.pool.stats() == {"swapped_pages": 0, "swap_outs": 1,
                                "swap_ins": 1, "pending_transfers": 0,
                                "transfers_in": 0,
                                "transfer_demotions": 0}


# ---------------------------------------------------------------------------
# Property: swap == recompute oracle == undisturbed, randomized schedules
# ---------------------------------------------------------------------------
def _shared_prefix_workload(rng, vocab, n_req, share_ratio):
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


def test_swap_property_matches_recompute_and_undisturbed(setup):
    """THE tentpole property: over randomized admission / EOS / shared-
    prefix / tight-pool schedules, the swap engine's streams are
    bit-identical to the recompute oracle at the same pool size AND to the
    undisturbed roomy-pool run, with pool + swap-manifest invariants
    audited after every step and the host tier fully drained."""
    pytest.importorskip("hypothesis", reason="hypothesis not installed")
    from hypothesis import HealthCheck, given, settings, strategies as st
    cfg, api, params = setup

    @settings(deadline=None, max_examples=5, print_blob=True,
              suppress_health_check=list(HealthCheck))
    @given(seed=st.integers(0, 2**16 - 1),
           num_pages=st.sampled_from([8, 9, 11, 14]),
           share_ratio=st.sampled_from([0.0, 0.5, 0.9]))
    def prop(seed, num_pages, share_ratio):
        rng = np.random.RandomState(seed)
        wl = _shared_prefix_workload(rng, cfg.vocab_size,
                                     int(rng.randint(4, 10)), share_ratio)
        undisturbed = _drive_checked(
            _engine(api, params, request_capacity=24,
                    page_policy="reserve"), wl)
        recompute = _drive_checked(
            _engine(api, params, request_capacity=24, num_pages=num_pages,
                    page_policy="demand", preempt_policy="recompute"), wl)
        eng = _engine(api, params, request_capacity=24, num_pages=num_pages,
                      page_policy="demand", preempt_policy="swap")
        got = _drive_checked(eng, wl)
        assert got == recompute == undisturbed
        assert eng.stats()["swapped_pages"] == 0
        assert not eng.pool.swap_manifest

    prop()


# ---------------------------------------------------------------------------
# Decode-time COW registration
# ---------------------------------------------------------------------------
def test_decode_cow_registers_generated_pages(setup):
    """A continuation prompt that replays (prompt + generated) of a
    finished request adopts the pages its DECODE filled — only when
    decode_cow is on; token streams are unchanged either way."""
    cfg, api, params = setup
    rng = np.random.RandomState(4)
    base = rng.randint(0, cfg.vocab_size, size=4).tolist()  # one full page

    def run(decode_cow):
        eng = _engine(api, params, request_capacity=24,
                      page_policy="demand", decode_cow=decode_cow)
        a = eng.submit(base, 8)
        eng.run(max_steps=80)
        assert a.status == DONE and len(a.generated) == 8
        keys_after_a = set(eng.pool.prefix_index)
        cont = base + [int(t) for t in a.generated]      # 12 tokens
        b = eng.submit(cont, 4)
        eng.run(max_steps=80)
        assert b.status == DONE
        eng.check_page_invariants()
        return eng, keys_after_a, a, b

    on_eng, on_keys, a_on, b_on = run(True)
    off_eng, off_keys, a_off, b_off = run(False)
    assert a_on.generated == a_off.generated
    assert b_on.generated == b_off.generated
    # decode filled the page holding tokens [4, 8) — only decode_cow
    # freezes it; admission-time registration stops at the prompt
    assert any(len(k) > len(base) for k in on_keys)
    assert all(len(k) <= len(base) for k in off_keys)
    assert on_eng.stats()["cow_hits"] > off_eng.stats()["cow_hits"]


# ---------------------------------------------------------------------------
# AOT: swap traffic performs zero post-warmup compilations
# ---------------------------------------------------------------------------
def test_warmed_engine_swap_traffic_zero_compiles(setup):
    """Warmup covers the sealed gather/scatter transfer path; a tight pool
    then drives real swap-outs and swap-ins with ZERO new XLA compiles."""
    cfg, api, params = setup
    eng = _engine(api, params, num_slots=3, num_microbatches=1,
                  request_capacity=24, num_pages=8, page_policy="demand",
                  prefix_sharing=False, preempt_policy="swap",
                  warmup=True, allow_swap=False)
    rng = np.random.RandomState(5)
    wl = [(rng.randint(0, cfg.vocab_size, size=4).tolist(), 14, None, 0)
          for _ in range(6)]
    _drive_checked(eng, wl)
    st = eng.stats()
    assert st["swap_outs"] > 0 and st["swap_ins"] > 0
    assert st["warmed"] and st["warmup_s"] > 0
    assert st["compile_stalls"] == [], st["compile_stalls"]
    assert st["post_warmup_compiles"] == 0, st["post_warmup_compiles"]


# ---------------------------------------------------------------------------
# Pipelined backends: restage memoization + staged swap transfer
# (subprocess on virtual devices)
# ---------------------------------------------------------------------------
def test_pipelined_restage_pair_memoized_no_stall(subproc):
    """PR 7 layout-tour gap, closed: a chain of swaps between two
    NON-planned layouts lazily AOT-warms each (from, to) restage pair once
    (no recorded stall), and a repeat of the same chain performs zero new
    XLA compilations."""
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        import repro.models.layers as L
        L.DEFAULT_DTYPE = jnp.float32
        from repro.configs import get_arch, reduced
        from repro.launch.mesh import make_mesh
        from repro.models.api import build_model
        from repro.serving import EngineConfig, ServingEngine, MONITOR
        from repro.serving.scheduler import DONE

        cfg = reduced(get_arch("llama3.2-1b"))
        api = build_model(cfg, max_seq=96)
        params = jax.tree.map(
            lambda x: x.astype(jnp.float32)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            api.init(jax.random.PRNGKey(0)))
        mesh = make_mesh((2, 2), ("pod", "data"))
        ec = EngineConfig(num_slots=4, num_stages=2, num_microbatches=2,
                          max_seq=96, prompt_capacity=8,
                          seal_boundary=False, page_size=4,
                          telemetry_interval=1000, warmup=True)
        eng = ServingEngine(api, mesh=mesh, config=ec, params=params,
                            backend="pipelined")
        assert eng.warmed and eng.kv_layout == "paged"
        targets = eng._swap_targets()
        assert len(targets) >= 2, targets
        a, b = targets[0], targets[1]
        # chain planned->a (toured, prewarmed), then a->b and b->a: the
        # first occurrence of each non-toured pair lazily warms off the
        # stall ledger
        assert eng.try_swap(a) and eng.try_swap(b) and eng.try_swap(a)
        assert eng.aot.post_freeze_stalls == []
        c1 = MONITOR.backend_compiles
        # the SAME pairs again must be compile-free (memoized dispatch)
        assert eng.try_swap(b) and eng.try_swap(a) and eng.try_swap(b)
        c2 = MONITOR.backend_compiles
        assert c2 == c1, (c1, c2)
        assert eng.aot.post_freeze_stalls == []
        assert ((a, b) in eng.backend._restage
                and (b, a) in eng.backend._restage)
        # the engine still serves to completion on the final layout
        rs = [eng.submit([1, 2, 3, 4], 4), eng.submit([5, 6, 7], 5)]
        eng.run(max_steps=120)
        assert all(r.status == DONE for r in rs), [r.status for r in rs]
        print("RESTAGE-MEMO OK", a, b)
    """, devices=4)


def test_pipelined_swap_preemption_token_exact(subproc):
    """The sharded staged page pools expose the same sealed gather/scatter
    primitives: swap preemption on the pipelined backend resumes with
    streams identical to the local-backend run of the same workload."""
    subproc("""
        import jax, jax.numpy as jnp, numpy as np
        import repro.models.layers as L
        L.DEFAULT_DTYPE = jnp.float32
        from repro.configs import get_arch, reduced
        from repro.launch.mesh import make_mesh
        from repro.models.api import build_model
        from repro.serving import EngineConfig, ServingEngine
        from repro.serving.scheduler import DONE

        cfg = reduced(get_arch("llama3.2-1b"))
        api = build_model(cfg, max_seq=96)
        params = jax.tree.map(
            lambda x: x.astype(jnp.float32)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            api.init(jax.random.PRNGKey(0)))
        mesh = make_mesh((2, 2), ("pod", "data"))
        rng = np.random.RandomState(6)
        wl = [(rng.randint(0, cfg.vocab_size, size=4).tolist(), 12)
              for _ in range(5)]

        def drive(backend, m, stages, mb):
            ec = EngineConfig(num_slots=2, num_stages=stages,
                              num_microbatches=mb, max_seq=96,
                              prompt_capacity=8, request_capacity=20,
                              seal_boundary=False, page_size=4,
                              num_pages=7, page_policy="demand",
                              prefix_sharing=False, preempt_policy="swap",
                              telemetry_interval=1000)
            eng = ServingEngine(api, mesh=m, config=ec, params=params,
                                backend=backend)
            reqs, k = [], 0
            while k < len(wl) or eng.scheduler.has_work():
                if k < len(wl):
                    reqs.append(eng.submit(*wl[k])); k += 1
                eng.step()
                eng.check_page_invariants()
                assert eng.steps < 400
            assert all(r.status == DONE for r in reqs)
            return eng, [r.generated for r in reqs]

        ep, got_p = drive("pipelined", mesh, 2, 2)
        el, got_l = drive("local", None, 1, 1)
        assert got_p == got_l, (got_p, got_l)
        st = ep.stats()
        assert st["swap_outs"] > 0 and st["swapped_pages"] == 0
        print("PIPELINED-SWAP OK", st["swap_outs"], st["swap_ins"])
    """, devices=4)
