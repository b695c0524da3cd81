#!/usr/bin/env python3
"""Chip smoke test: serve llama3.2-1b at published widths on TPU v5e.

    python chip_smoke.py             # one chip: served path + kernel checks
    python chip_smoke.py --chips 4   # four chips: the pipelined trust-domain
                                     # path, compared with one chip

One chip: the model (16 layers, d_model 2048, vocab 128256, bf16, random
weights from ``--seed``) is served through ``ServingEngine`` with the paged
KV cache, demand paging, sealed swap preemption, AOT warmup and the Pallas
kernels. A pool tight enough to swap requests out and back in runs the page
cipher at a real page count. Then the paged-attention kernel's decode
logits are checked against the jnp reference on one cache that holds a
real prefill and fifteen random rows, and against the prefill's own logits.

Four chips: the same model on ``PagedPipelinedBackend`` over a (4, 1)
("pod", "data") mesh, one stage per chip, against ``PagedLocalBackend`` on
the first chip: first-step logits must agree with sealing off, and with
int8 sealing at every cut every request must be served.

Everything runs in this one process. Any failed check raises, so the exit
code is non-zero. Without a TPU it exits non-zero and prints no result.
The last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models.api import build_model  # noqa: E402
from repro.serving import EngineConfig, ServingEngine  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "llama3.2-1b"
SLOTS = 16
PAGE_SIZE = 16
REQUEST_CAPACITY = 2048          # 128 pages per slot
PROMPT_CAPACITY = 1024
PROMPT_MIN = 256
MAX_NEW = 32
# Logit tolerance, relative to the largest reference logit. Both sides of
# each comparison read the same bf16 weights and KV and differ only in
# reduction order and batching, so each of the 16 layers can round its
# bf16 output (8 significant bits, 2**-8 relative) differently once; at
# worst those differences add up linearly through the residual stream.
TOL_REL = 16 * 2.0 ** -8


def gib(n: float) -> str:
    return f"{n / 2**30:.3f} GiB"


def tree_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def bytes_in_use(devices) -> str:
    return ", ".join(f"{d.id}: {gib(d.memory_stats()['bytes_in_use'])}"
                     for d in devices)


def max_rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all() and np.isfinite(want).all()
    return float(np.abs(got - want).max() / np.abs(want).max())


def make_prompts(rng, n: int, lo: int, hi: int, vocab: int):
    return [rng.randint(0, vocab, size=int(rng.randint(lo, hi + 1))).tolist()
            for _ in range(n)]


def serve(eng: ServingEngine, prompts, max_new: int):
    """Submit every prompt, drive the engine until it drains, and check
    that every request finished and none failed."""
    reqs = [eng.submit(p, max_new) for p in prompts]
    while eng.scheduler.has_work():
        eng.step()
        if eng.stalled:
            raise RuntimeError(f"engine stalled: {eng.stall_reason}")
    st = eng.stats()
    done = sum(r.status == "done" for r in reqs)
    print(f"requests: {done} done of {len(reqs)} submitted, "
          f"failed={len(st['failed_requests'])}, "
          f"tokens_out={st['tokens_out']}, steps={st['steps']}")
    assert done == len(reqs) and not st["failed_requests"], st
    assert all(len(r.generated) == max_new for r in reqs)
    return reqs, st


# ---------------------------------------------------------------------------
# One chip: the served path
# ---------------------------------------------------------------------------
def one_chip_serve(api, params, *, slots, prompt_capacity, request_capacity,
                   page_size, num_pages, prompts, max_new):
    ec = EngineConfig(
        num_slots=slots, num_stages=1, num_microbatches=1,
        prompt_capacity=prompt_capacity, request_capacity=request_capacity,
        page_size=page_size, num_pages=num_pages, page_policy="demand",
        preempt_policy="swap", warmup=True, use_kernel=True)
    eng = ServingEngine(api, config=ec, params=params, backend="local")
    seg = api.model.segments[0].name
    print(f"engine: backend={eng.backend_kind} kv_layout={eng.kv_layout} "
          f"pages={eng.pool.num_pages} x {page_size} tokens, "
          f"{eng.pages_per_slot} pages/slot, KV pool bytes="
          f"{gib(tree_bytes(eng.backend.cache[seg]))}")
    print(f"warmup: {eng.warmup_s:.1f} s, "
          f"{sum(len(f.signatures) for f in eng.aot.fns.values())} "
          f"signatures over {len(eng.aot.fns)} functions")
    _, st = serve(eng, prompts, max_new)
    print(f"post-warmup compiles: {st['post_warmup_compiles']}, "
          f"stalls: {st['compile_stalls']}")
    print(f"preemptions={st['preemptions']} swap_outs={st['swap_outs']} "
          f"swap_ins={st['swap_ins']} swap_fallbacks={st['swap_fallbacks']}")
    assert st["post_warmup_compiles"] == 0 and not st["compile_stalls"], st
    assert st["preemptions"] > 0 and st["swap_outs"] > 0 \
        and st["swap_ins"] > 0, st
    return eng


def kernel_check(api, params, *, slots, pages_per_slot, page_size,
                 prompt, seed):
    """Decode logits of the paged-attention kernel vs the jnp reference on
    one cache: row 0 holds the prefill of ``prompt[:-1]`` and decodes its
    last token (so its logits must also match the prefill of the whole
    prompt); the other rows attend over random KV at random lengths."""
    cfg, seg = api.cfg, api.model.segments[0].name
    P = len(prompt)
    num_pages = slots * pages_per_slot + 1
    cache = api.init_paged_cache(slots, num_pages, page_size, pages_per_slot)
    rng = np.random.RandomState(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    pools = tuple(jax.random.normal(k, p.shape, jnp.float32).astype(p.dtype)
                  for k, p in zip(keys, cache[seg]))
    bucket = 1 << (P - 1).bit_length()
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :P] = prompt
    pf_logits, pf_caches = jax.jit(api.prefill_at_fn)(
        params, {"tokens": jnp.asarray(toks), "prompt_len": jnp.int32(P)})
    # row 0's pages are 1 .. pages_per_slot in order: token t of the prompt
    # lives in page 1 + t // page_size at offset t % page_size
    n_pg = -(-(P - 1) // page_size)
    L, KVH, D = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    pools = tuple(
        pool.at[:, 1:1 + n_pg].set(
            kv[:, 0, :, :n_pg * page_size]
            .reshape(L, KVH, n_pg, page_size, D).transpose(0, 2, 1, 3, 4))
        for pool, kv in zip(pools, pf_caches[seg]))
    cache[seg] = pools
    cache["block_tables"] = jnp.asarray(
        1 + np.arange(slots * pages_per_slot, dtype=np.int32)
        .reshape(slots, pages_per_slot))
    seq_lens = rng.randint(1, pages_per_slot * page_size, size=slots)
    seq_lens[0] = P - 1
    cache["seq_lens"] = jnp.asarray(seq_lens.astype(np.int32))
    tokens = rng.randint(0, cfg.vocab_size, size=(slots, 1)).astype(np.int32)
    tokens[0, 0] = prompt[-1]
    batch = {"tokens": jnp.asarray(tokens)}
    out = {}
    for use_kernel in (True, False):
        compiled = jax.jit(functools.partial(
            api.decode_paged_fn, use_kernel=use_kernel)).lower(
                params, cache, batch).compile()
        if use_kernel:
            assert "tpu_custom_call" in compiled.as_text(), \
                "kernel-path decode has no Pallas kernel (tpu_custom_call)"
            print("kernel-path decode executable contains tpu_custom_call")
        out[use_kernel] = np.asarray(compiled(params, cache, batch)[0])
    err_batch = max_rel_err(out[True], out[False])
    err_prefill = max_rel_err(out[True][0], pf_logits[0])
    print(f"decode logits, kernel vs jnp reference, {slots} rows "
          f"(seq_lens {seq_lens.min()}..{seq_lens.max()}): max rel err "
          f"{err_batch:.3e} (tol {TOL_REL:.3e})")
    print(f"decode logits, kernel after prefill of {P - 1} tokens vs "
          f"prefill of {P}: max rel err {err_prefill:.3e} "
          f"(tol {TOL_REL:.3e}), argmax {int(out[True][0].argmax())} vs "
          f"{int(np.asarray(pf_logits[0]).argmax())}")
    assert err_batch <= TOL_REL and err_prefill <= TOL_REL, \
        (err_batch, err_prefill)


def run_one_chip(api, params, seed: int) -> None:
    dev = jax.devices()[0]
    cfg = api.cfg
    rng = np.random.RandomState(seed)
    # a pool of 320 pages holds about seven 256-1024 token requests, so
    # decode growth preempts: requests are sealed out to the host and back
    prompts = make_prompts(rng, 24, PROMPT_MIN, PROMPT_CAPACITY,
                           cfg.vocab_size)
    eng = one_chip_serve(
        api, params, slots=SLOTS, prompt_capacity=PROMPT_CAPACITY,
        request_capacity=REQUEST_CAPACITY, page_size=PAGE_SIZE,
        num_pages=321, prompts=prompts, max_new=MAX_NEW)
    ms = dev.memory_stats()
    print(f"memory after serving: bytes_in_use={gib(ms['bytes_in_use'])} "
          f"peak={gib(ms['peak_bytes_in_use'])}")
    del eng
    gc.collect()
    kernel_check(api, params, slots=SLOTS,
                 pages_per_slot=REQUEST_CAPACITY // PAGE_SIZE,
                 page_size=PAGE_SIZE, prompt=prompts[0], seed=seed)


# ---------------------------------------------------------------------------
# Four chips: the pipelined trust-domain path vs one chip
# ---------------------------------------------------------------------------
class LogitsTap:
    """Delegates to a decode backend and keeps the first decode step's
    logits on the host."""

    def __init__(self, backend):
        self._backend = backend
        self.first = None

    def __getattr__(self, name):
        return getattr(self._backend, name)

    def step(self, tokens, key):
        logits = self._backend.step(tokens, key)
        if self.first is None:
            self.first = np.asarray(logits)
        return logits


def pipeline_engine(api, params, mesh, *, seal: bool, **kw):
    """``PagedPipelinedBackend`` with one stage per pod of ``mesh``, or
    ``PagedLocalBackend`` on the first device when ``mesh`` is None."""
    stages = 1 if mesh is None else mesh.shape["pod"]
    ec = EngineConfig(num_stages=stages, num_microbatches=stages,
                      seal_boundary=seal, use_kernel=True, allow_swap=False,
                      **kw)
    return ServingEngine(api, mesh=mesh, config=ec, params=params,
                         backend="local" if mesh is None else "pipelined")


def staged_bytes_per_device(tree):
    out = {}
    for a in jax.tree.leaves(tree):
        for sh in a.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def run_four_chips(api, params, seed: int, chips: int) -> None:
    devices = jax.devices()
    assert len(devices) == chips, (len(devices), chips)
    cfg, seg = api.cfg, api.model.segments[0].name
    rng = np.random.RandomState(seed)
    prompts = make_prompts(rng, SLOTS, PROMPT_MIN, PROMPT_CAPACITY,
                           cfg.vocab_size)
    kw = dict(num_slots=SLOTS, prompt_capacity=PROMPT_CAPACITY,
              request_capacity=REQUEST_CAPACITY, page_size=PAGE_SIZE)

    def first_step_logits(eng):
        eng.backend = tap = LogitsTap(eng.backend)
        serve(eng, prompts, MAX_NEW)
        return tap.first

    one = pipeline_engine(api, params, None, seal=False, **kw)
    print(f"one chip: backend={one.backend_kind} on device "
          f"{devices[0].id}")
    want = first_step_logits(one)
    # an engine's device buffers sit in reference cycles: collect them so
    # the bytes printed below are what the next engine holds
    del one
    gc.collect()

    mesh = make_mesh((chips, 1), ("pod", "data"))
    print(f"bytes in use before staging: {bytes_in_use(devices)}")
    pipe = pipeline_engine(api, params, mesh, seal=False, **kw)
    blocks = pipe.stage_blocks
    staged = pipe.backend.staged_params[seg]
    per_dev = staged_bytes_per_device(staged)
    print(f"{chips} chips: backend={pipe.backend_kind} "
          f"stage_blocks={blocks} of {sum(blocks)}")
    print("staged block params per device: "
          + ", ".join(f"{d}: {gib(n)}" for d, n in sorted(per_dev.items())))
    print(f"staged KV pool per device: "
          + ", ".join(f"{d}: {gib(n)}" for d, n in sorted(
              staged_bytes_per_device(pipe.backend.state[0]).items())))
    print(f"bytes in use after staging: {bytes_in_use(devices)}")
    total = tree_bytes(staged)
    bps = max(blocks)
    for a in jax.tree.leaves(staged):
        assert a.sharding.spec[0] == "pod", a.sharding
    for d in devices:
        assert abs(per_dev[d.id] - total / chips) <= 1, (per_dev, total)
    print(f"each chip holds {bps} block slots of {chips * bps} "
          f"({gib(total / chips)} of {gib(total)} staged block params)")
    before = bytes_in_use(devices)
    err = max_rel_err(first_step_logits(pipe), want)
    print(f"bytes in use before / after serving: {before} / "
          f"{bytes_in_use(devices)}")
    print(f"first decode step logits, {chips}-stage pipelined vs one chip "
          f"(seal off): max rel err {err:.3e} (tol {TOL_REL:.3e})")
    del pipe
    gc.collect()

    sealed = pipeline_engine(api, params, mesh, seal=True, **kw)
    print(f"{chips} chips, int8 seal at every cut: "
          f"stage_blocks={sealed.stage_blocks}")
    # int8 quantization at the cuts changes the logits by design: only
    # reported, the check is that every request is served
    err_sealed = max_rel_err(first_step_logits(sealed), want)
    print(f"first decode step logits, sealed vs one chip: max rel err "
          f"{err_sealed:.3e}")
    assert err <= TOL_REL, err


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found platform "
                 f"{devices[0].platform!r}")
    enable_compile_cache()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x {len(devices)}")
    cfg = get_arch(ARCH)
    api = build_model(cfg, max_seq=REQUEST_CAPACITY)
    t0 = time.perf_counter()
    params = jax.jit(api.init)(jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} dtype={params['embed'].dtype} "
          f"param bytes={gib(tree_bytes(params))} "
          f"(init {time.perf_counter() - t0:.1f} s)")
    print(f"bytes in use after init: {bytes_in_use(devices)}")
    if args.chips == 1:
        run_one_chip(api, params, args.seed)
    else:
        run_four_chips(api, params, args.seed, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
