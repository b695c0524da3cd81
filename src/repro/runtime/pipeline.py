"""Serdab pipelined serving over the ``pod`` mesh axis.

This is the paper's core mechanism as a first-class runtime feature: the
block stack is split into ``num_stages`` contiguous stages (boundaries from
the placement solver), stage s lives on pod s, and a stream of decode
microbatches rotates through the stages GPipe-style — while pod 1 decodes
microbatch m for blocks [B/2, B), pod 0 is already decoding microbatch m+1
for blocks [0, B/2). Boundary activations are sealed (int8 quantize +
keystream XOR — kernels/seal.py) before crossing the DCN, exactly the
paper's enclave-to-enclave discipline, and the quantization doubles as 4x
boundary compression.

Implementation: ``jax.shard_map`` manual over {pod} only — data/model axes
stay GSPMD-managed inside each stage, so TP/EP/sequence-sharded caches
compose with pipelining. With ``use_kernel`` (Pallas attention and seal
kernels, which cannot be partitioned automatically) every axis is manual.
The tick loop is a ``lax.scan``; communication is one ``ppermute`` ring
per tick.

Applicability: any model whose body is ONE homogeneous scanned segment
(dense, VLM, Qwen-MoE, xLSTM, Hymba). Moonshot's dense stem and Whisper's
encoder make them two-segment models — they serve multi-pod via batch
sharding instead (DESIGN.md §Arch-applicability).

Stage boundaries need not be even: ``stage_blocks`` takes the solver's
per-stage block counts (e.g. 28 blocks as 10/9/9). Uneven stages are padded
to the widest stage; padded slots replicate a real block's params/cache and
are masked out of the scan, so logits match the unpipelined decode path
exactly (DESIGN.md §Planner).

Compile-stability contract (DESIGN.md §AOT warmup & chunked prefill): a
PipelinedDecoder's jitted entry points — ``build()``'s step,
``build_stage_probe()``'s probe and the serving backends' chunk closure —
are shape-stable for a FIXED ``stage_blocks`` layout, so the engine's
``warmup()`` can precompile them and a steady-state serve dispatches with
zero new XLA compilations. Two sharp edges the serving layer accounts for:
(1) shard_map state arrays change *sharding* between the first call
(fresh, unsharded ``init_paged_cache`` output) and steady state
(pod-sharded step output), and jit's dispatch cache keys on
(shape, sharding) — both variants must be warmed; (2) ``restage_cache``'s
composed gather is shaped by the specific (old layout, new layout) PAIR —
the warmup tour covers planned↔target pairs, and the backends lazily
AOT-warm and memoize any other pair on first use (keyed by the pair in the
per-layout decoder cache), so a chain of swaps between two non-planned
layouts pays at most one wall-clock warm per pair and NO recorded compile
stall on repeats. Decoders themselves are cached per layout by the
backends (``_layouts``): rebuilding a decoder for a layout already seen
would discard the warmed dispatch caches with it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.enclave import sealing
from repro.models.api import ModelAPI
from repro.models import layers as L
from repro.sharding import rules as R


def pipeline_applicable(api: ModelAPI) -> bool:
    model = api.model
    return (hasattr(model, "segments") and len(model.segments) == 1)


def _batch_slice(tree, start, size, axis=1):
    return jax.tree.map(
        lambda x: jax.lax.dynamic_slice_in_dim(x, start, size, axis=axis), tree)


def _batch_update(tree, update, start, axis=1):
    return jax.tree.map(
        lambda x, u: jax.lax.dynamic_update_slice_in_dim(x, u, start, axis=axis),
        tree, update)


@dataclasses.dataclass
class PipelinedDecoder:
    """Builds a jit-able pipelined decode step for one mesh."""

    api: ModelAPI
    mesh: Mesh
    num_stages: int
    num_microbatches: int
    seal_boundary: bool = True
    use_kernel: bool = False            # Pallas path on TPU
    stage_blocks: Optional[Sequence[int]] = None   # per-stage block counts
    stage_devices: Optional[Sequence[str]] = None  # per-stage device names

    @classmethod
    def from_spec(cls, api: ModelAPI, mesh: Mesh, spec,
                  num_microbatches: int, **kw) -> "PipelinedDecoder":
        """Build a decoder directly from a planner ``PlacementSpec``: stage s
        runs segment s (``spec.segments[s]``) on pod s. The spec's device
        order is the pipeline order — non-prefix placements (untrusted
        segments interleaved mid-chain) execute exactly like prefix ones;
        the trust domain only changes what the cost model charged and which
        boundaries the sealing discipline covers."""
        n = api.model.segments[0].n
        assert spec.num_layers == n, (spec.num_layers, n)
        return cls(api, mesh, num_stages=spec.num_segments,
                   num_microbatches=num_microbatches,
                   stage_blocks=spec.stage_sizes(),
                   stage_devices=spec.devices(), **kw)

    def __post_init__(self):
        model = self.api.model
        assert pipeline_applicable(self.api), \
            "pipelined serve needs a single homogeneous segment"
        self.seg = model.segments[0]
        S = self.num_stages
        if self.stage_blocks is None:
            assert self.seg.n % S == 0, \
                f"{self.seg.n} blocks not divisible into {S} stages; " \
                f"pass stage_blocks= for uneven boundaries"
            counts = (self.seg.n // S,) * S
        else:
            counts = tuple(int(c) for c in self.stage_blocks)
            assert len(counts) == S, (counts, S)
            assert all(c > 0 for c in counts), counts
            assert sum(counts) == self.seg.n, (counts, self.seg.n)
        if self.stage_devices is not None:
            self.stage_devices = tuple(self.stage_devices)
            assert len(self.stage_devices) == S, (self.stage_devices, S)
        self.stage_counts = counts
        self.bps = max(counts)          # padded per-stage block count
        self.uniform = len(set(counts)) == 1
        starts = np.concatenate([[0], np.cumsum(counts)])
        # gather: staged slot (s, j) holds block starts[s] + min(j, c_s - 1);
        # padded slots replicate the stage's last block (finite values, then
        # masked out of the scan)
        self._gather_idx = np.stack(
            [starts[s] + np.minimum(np.arange(self.bps), counts[s] - 1)
             for s in range(S)]).reshape(-1)
        # scatter: block i lives at staged slot stage(i) * bps + offset
        self._scatter_idx = np.concatenate(
            [s * self.bps + np.arange(counts[s]) for s in range(S)])
        self._mask = np.stack(
            [np.arange(self.bps) < counts[s] for s in range(S)])

    # -- parameter / cache reshaping (leading stage dim, sharded over pod) --
    def _on_pods(self, x):
        """Pin a staged leaf's stage dim to the pod axis (stage s on pod s),
        inside a jit or eagerly; a decoder without a mesh only reshapes."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P("pod", *([None] * (x.ndim - 1)))))

    def _stage_tree(self, tree):
        """[n_blocks, ...] leaves -> [num_stages, bps, ...] (gather-padded
        when stages are uneven, plain reshape when even), stage dim on the
        pod axis."""
        S, bps = self.num_stages, self.bps
        if self.uniform:
            return jax.tree.map(
                lambda x: self._on_pods(x.reshape((S, bps) + x.shape[1:])),
                tree)
        idx = jnp.asarray(self._gather_idx)
        return jax.tree.map(
            lambda x: self._on_pods(jnp.take(x, idx, axis=0).reshape(
                (S, bps) + x.shape[1:])), tree)

    def stage_params(self, params):
        seg = dict(params)
        seg[self.seg.name] = self._stage_tree(params[self.seg.name])
        return seg

    def stage_cache(self, cache):
        return self._stage_tree(cache[self.seg.name]), cache["len"]

    def unstage_cache(self, staged, new_len):
        S, bps = self.num_stages, self.bps
        if self.uniform:
            body = jax.tree.map(
                lambda x: x.reshape((self.seg.n,) + x.shape[2:]), staged)
        else:
            idx = jnp.asarray(self._scatter_idx)
            body = jax.tree.map(
                lambda x: jnp.take(
                    x.reshape((S * bps,) + x.shape[2:]), idx, axis=0), staged)
        return {self.seg.name: body, "len": new_len}

    def restage_cache(self, staged_cache, new_dec: "PipelinedDecoder"):
        """Migrate a prestaged cache from this decoder's stage layout to
        ``new_dec``'s (a live re-plan swap). Equivalent to unstage followed by
        ``new_dec.stage_cache`` but composes the scatter and gather into a
        single ``jnp.take`` per leaf, so in-flight KV state moves to the new
        boundaries without a host round-trip. Accepts the prestaged tuple
        ``(staged, len)`` or ``(staged, len, start)`` and returns the same
        arity."""
        assert new_dec.seg.n == self.seg.n, (new_dec.seg.n, self.seg.n)
        body, *rest = staged_cache
        S2, bps2 = new_dec.num_stages, new_dec.bps
        idx = jnp.asarray(self._scatter_idx[new_dec._gather_idx])
        new_body = jax.tree.map(
            lambda x: new_dec._on_pods(jnp.take(
                x.reshape((self.num_stages * self.bps,) + x.shape[2:]),
                idx, axis=0).reshape((S2, bps2) + x.shape[2:])), body)
        return (new_body, *rest)

    # -- specs ---------------------------------------------------------------
    def _param_specs_tree(self, staged):
        def spec(path_has_stage, x):
            if path_has_stage:
                return P("pod", *([None] * (x.ndim - 1)))
            return P(*([None] * x.ndim))
        return {k: jax.tree.map(functools.partial(spec, k == self.seg.name), v)
                for k, v in staged.items()}

    # -- placement: commit staged trees to the mesh ONCE, outside the step ---
    def place_params(self, params):
        """Stage ``params`` and commit them to the mesh: stage s's blocks on
        pod s, embedding / final norm replicated. Committed arrays that
        already match the step's shard_map specs are never moved again, so
        no step copies the model."""
        staged = self.stage_params(params)
        return {k: v if k == self.seg.name else self.place_replicated(v)
                for k, v in staged.items()}

    def place_replicated(self, tree):
        """Commit a tree replicated over the mesh (block tables, lengths)."""
        return jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(self.mesh, P())), tree)

    @staticmethod
    def stage_shard(tree, s: int):
        """Stage s's [1, bps, ...] block of a pod-sharded staged tree, read
        on the device that holds it (no gather), plus that device."""
        leaves, treedef = jax.tree.flatten(tree)
        shards = [next(sh for sh in a.addressable_shards
                       if sh.index[0].start == s) for a in leaves]
        return (jax.tree.unflatten(treedef, [sh.data for sh in shards]),
                shards[0].device)

    # -- one stage's block scan (shared by the tick loop and the telemetry
    # -- stage probe) --------------------------------------------------------
    def _stage_run(self, blk_params, blk_cache, blk_mask, x, cache_len,
                   start=None, paged=None):
        cfg, seg = self.api.cfg, self.seg
        if paged is not None:
            # paged cache: per-row 0-based positions from seq_lens; the
            # block cache is the stage's slice of the shared page pools
            _, sl_mb = paged
            positions = sl_mb[:, None]
            pos3 = None
            if cfg.pos_type == "mrope":
                pos3 = jnp.tile(sl_mb[:, None, None], (1, 1, 3))
            kw = {"paged": paged, "paged_kernel": self.use_kernel}
        else:
            positions = jnp.full((1, 1), cache_len, jnp.int32)
            pos3 = None
            if cfg.pos_type == "mrope":
                pos3 = jnp.full((x.shape[0], 1, 3), cache_len, jnp.int32)
            kw = {} if start is None else {"start": start}

        def step(carry, xs):
            p, c, m = xs
            out, new_c = seg.apply_fn(p, carry, positions, mode="decode",
                                      cache=c, cache_len=cache_len,
                                      pos3=pos3, **kw)
            # padded slots (uneven stages) pass the carry through and
            # leave their (replicated) cache untouched
            out = jnp.where(m, out, carry)
            new_c = jax.tree.map(lambda a, b: jnp.where(m, a, b),
                                 new_c, c)
            return out, new_c

        return jax.lax.scan(step, x, (blk_params, blk_cache, blk_mask))

    def build_stage_probe(self, paged: bool = False):
        """A jit-able single-stage runner for per-stage wall-time telemetry:
        ``probe(blk_params, blk_cache, blk_mask, x, cache_len)`` executes one
        stage's block scan exactly as a pipeline tick would (minus seal /
        ppermute) so the host can time each stage independently. The caller
        passes stage s's [1, bps, ...] shards (``stage_shard``) and the
        other inputs on that stage's device, and times
        ``jax.block_until_ready(probe(...))`` — stage s runs on its own
        chip. With ``paged=True`` the signature is ``probe(blk_params,
        blk_pool, blk_mask, x, bt, sl)`` (whole-pool stage shard, block
        table + seq_lens for the probed rows)."""
        def first(tree):
            return jax.tree.map(lambda a: a[0], tree)

        if paged:
            def probe(blk_params, blk_cache, blk_mask, x, bt, sl):
                h, _ = self._stage_run(first(blk_params), first(blk_cache),
                                       blk_mask, x, None, paged=(bt, sl))
                return h
        else:
            def probe(blk_params, blk_cache, blk_mask, x, cache_len):
                rows = jax.tree.map(lambda a: a[0, :, :x.shape[0]],
                                    blk_cache)
                h, _ = self._stage_run(first(blk_params), rows, blk_mask, x,
                                       cache_len)
                return h
        return probe

    # -- the step -------------------------------------------------------------
    def build(self, prestaged_params: bool = False,
              prestaged_cache: bool = False, per_slot_start: bool = False,
              paged: bool = False):
        """per_slot_start: the cache argument becomes a 3-tuple
        ``(staged, cache_len, start)`` with ``start`` a per-slot [B] int32 of
        first-valid absolute positions (continuous-batching mask); implies
        ``prestaged_cache``.

        paged: the cache argument is ``(staged_pools, block_tables,
        seq_lens)`` — prestaged per-layer page pools (stage-major, pod
        sharded; *no* batch dim: pages are shared, block tables say which
        rows own which pages) plus the per-slot [B, MP] block tables and
        [B] seq_lens, replicated over pods. Every microbatch's stage scan
        scatters its rows' new tokens into disjoint pages of the same pool,
        so the pool is carried whole across ticks instead of batch-sliced;
        warm-up/drain ticks are masked out before committing (their
        boundary activations are garbage). Positions are per-row 0-based —
        the continuous-batching ``start`` mask is unnecessary by
        construction.

        Demand paging / COW contract (DESIGN.md §Demand paging): block
        tables may reference ref-counted pages shared across rows or
        frozen in the engine's prefix index. The decoder itself never
        needs to know — the engine guarantees, before every step, that
        each row's *next write position* is backed by a private
        (refcount-1) page, forking shared pages host-side first; reads
        gather freely across shared pages. ``restage_cache`` migration is
        refcount-oblivious by the same token: page ids are stable across
        a boundary swap (only the layer→stage layout of the pools moves),
        so host-side refcounts and block tables ride along unchanged."""
        api, seg, S = self.api, self.seg, self.num_stages
        nm, bps = self.num_microbatches, self.bps
        cfg = api.cfg
        model = api.model
        mesh = self.mesh
        seal_on = self.seal_boundary
        use_kernel = self.use_kernel
        if per_slot_start:
            assert prestaged_cache, "per_slot_start implies prestaged_cache"
        assert not (per_slot_start and paged)
        stage_run = self._stage_run

        def pipeline_body(params, staged_cache, stage_mask, tokens, starts,
                          cache_len, key):
            """Runs manual over pod. tokens: [nm, B_mb, 1] (replicated over
            pod); staged leaves [1, bps, B, ...] (pod-sharded stage dim);
            stage_mask [1, bps] marks real (non-padding) block slots;
            starts: [nm, B_mb] per-slot first valid positions (replicated,
            ignored unless per_slot_start). In paged mode staged leaves are
            [1, bps, N, KVH, Pg, D] pools and ``starts`` is the pair
            ``(block_tables [nm, B_mb, MP], seq_lens [nm, B_mb])``."""
            s_idx = jax.lax.axis_index("pod")
            my_params = jax.tree.map(lambda x: x[0], params[seg.name])
            my_cache = jax.tree.map(lambda x: x[0], staged_cache)
            my_mask = stage_mask[0]
            B_mb = tokens.shape[1]
            d = cfg.d_model
            V = cfg.vocab_size

            def embed(tok):
                e = jnp.take(params["embed"], tok, axis=0)
                return e.astype(L.DEFAULT_DTYPE)

            def head(h):
                hn = L.rmsnorm(h[:, -1], params["ln_f"], cfg.norm_eps)
                w = (params["embed"].T if cfg.tie_embeddings
                     else params["head"])
                return jnp.einsum("bd,dv->bv", hn, w,
                                  preferred_element_type=jnp.float32)

            # sealed boundary payload carried between ticks
            zero_h = jnp.zeros((B_mb, 1, d), L.DEFAULT_DTYPE)
            if seal_on:
                c0, sc0 = sealing.seal_array(zero_h, jnp.uint32(0), 0,
                                             use_kernel=use_kernel)
                recv0 = (c0, sc0)
            else:
                recv0 = zero_h

            outputs0 = jnp.zeros((nm, B_mb, V), jnp.float32)

            def tick(carry, t):
                recv, cache_st, outputs = carry
                m_my = t - s_idx
                valid = (m_my >= 0) & (m_my < nm)
                m_idx = jnp.clip(m_my, 0, nm - 1)

                # stage input: stage 0 embeds its microbatch, others unseal
                tok = jax.lax.dynamic_index_in_dim(tokens, m_idx, 0,
                                                   keepdims=False)
                x0 = embed(tok)
                if seal_on:
                    step_ctr = jnp.uint32(t)
                    h_recv = sealing.unseal_array(
                        recv[0], recv[1], (B_mb, 1, d), key, step_ctr,
                        dtype=L.DEFAULT_DTYPE, use_kernel=use_kernel)
                else:
                    h_recv = recv
                x_in = jnp.where(s_idx == 0, x0, h_recv)

                if paged:
                    # pages are shared across rows — run the stage over the
                    # whole pool with this microbatch's table rows; commit
                    # only on valid ticks (warm-up/drain inputs are garbage)
                    bt_mb = jax.lax.dynamic_index_in_dim(starts[0], m_idx, 0,
                                                         keepdims=False)
                    sl_mb = jax.lax.dynamic_index_in_dim(starts[1], m_idx, 0,
                                                         keepdims=False)
                    h, new_pool = stage_run(my_params, cache_st, my_mask,
                                            x_in, None, paged=(bt_mb, sl_mb))
                    cache_st = jax.tree.map(
                        lambda new, old: jnp.where(valid, new, old),
                        new_pool, cache_st)
                else:
                    # my stage's cache slice for this microbatch
                    cache_sl = _batch_slice(cache_st, m_idx * B_mb, B_mb)
                    st = None
                    if per_slot_start:
                        st = jax.lax.dynamic_index_in_dim(starts, m_idx, 0,
                                                          keepdims=False)
                    h, new_sl = stage_run(my_params, cache_sl, my_mask, x_in,
                                          cache_len, start=st)
                    # only commit the slice when this tick is valid for me
                    new_sl = jax.tree.map(
                        lambda new, old: jnp.where(valid, new, old),
                        new_sl, cache_sl)
                    cache_st = _batch_update(cache_st, new_sl, m_idx * B_mb)

                # seal + rotate boundary activation to the next stage
                if seal_on:
                    payload = sealing.seal_array(h, key, jnp.uint32(t + 1),
                                                 use_kernel=use_kernel)
                else:
                    payload = h
                perm = [(i, (i + 1) % S) for i in range(S)]
                recv_next = jax.tree.map(
                    lambda x: jax.lax.ppermute(x, "pod", perm), payload)

                # last stage emits logits for microbatch t - (S-1)
                lg = head(h)
                m_out = jnp.clip(t - (S - 1), 0, nm - 1)
                write = (s_idx == S - 1) & (t >= S - 1)
                upd = jax.lax.dynamic_update_slice_in_dim(
                    outputs, lg[None], m_out, axis=0)
                outputs = jnp.where(write, upd, outputs)
                return (recv_next, cache_st, outputs), None

            (_, cache_fin, outputs), _ = jax.lax.scan(
                tick, (recv0, my_cache, outputs0), jnp.arange(nm + S - 1))
            cache_out = jax.tree.map(lambda x: x[None], cache_fin)
            return outputs, cache_out

        # ---- shard_map wrapper ------------------------------------------
        def step_fn(params, cache, batch, key):
            tokens = batch["tokens"]                   # [B, 1]
            B = tokens.shape[0]
            B_mb = B // nm
            tok_stream = tokens.reshape(nm, B_mb, 1)
            # uneven stages make staging a real gather (not a free reshape);
            # serving loops should stage params/cache once outside the loop
            # (stage_params/stage_cache + prestaged_*=True) rather than
            # re-gather per token — the cache round-trips twice otherwise
            staged_params = params if prestaged_params \
                else self.stage_params(params)
            start_vec = None
            bt_vec = sl_vec = None
            if paged:
                staged_cache, bt_vec, sl_vec = cache
                cache_len = jnp.int32(0)                    # unused
                starts = (bt_vec.reshape(nm, B_mb, -1),
                          sl_vec.reshape(nm, B_mb))
                starts_spec = (P(), P())
            elif per_slot_start:
                staged_cache, cache_len, start_vec = cache
                starts = start_vec.reshape(nm, B_mb)
                starts_spec = P()
            else:
                if prestaged_cache:
                    staged_cache, cache_len = cache
                else:
                    staged_cache, cache_len = self.stage_cache(cache)
                starts = jnp.zeros((nm, B_mb), jnp.int32)   # unused
                starts_spec = P()
            stage_mask = jnp.asarray(self._mask)

            param_specs = self._param_specs_tree(staged_params)
            cache_specs = jax.tree.map(
                lambda x: P("pod", *([None] * (x.ndim - 1))), staged_cache)
            body = functools.partial(pipeline_body)
            # a Pallas kernel cannot be partitioned automatically, so with
            # use_kernel every mesh axis is manual (each stage's data
            # replicas compute the same) and no GSPMD rule applies inside
            if use_kernel:
                manual, rules = set(mesh.axis_names), contextlib.nullcontext()
            else:
                manual, rules = {"pod"}, R.axis_rules(mesh, R.PIPE_RULES)
            with rules:
                outputs, new_cache = jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(param_specs, cache_specs, P("pod", None),
                              P(), starts_spec, P(), P()),
                    out_specs=(P("pod"), cache_specs),
                    axis_names=manual, check_vma=False,
                )(staged_params, staged_cache, stage_mask, tok_stream,
                  starts, cache_len, key)
            # stages stack outputs along dim 0; the last nm rows are real
            logits = outputs[-nm:].reshape(B, -1)
            if paged:
                cache_out = (new_cache, bt_vec,
                             jnp.where(sl_vec > 0, sl_vec + 1, 0))
            elif per_slot_start:
                cache_out = (new_cache, cache_len + 1, start_vec)
            elif prestaged_cache:
                cache_out = (new_cache, cache_len + 1)
            else:
                cache_out = self.unstage_cache(new_cache, cache_len + 1)
            return logits, cache_out

        return step_fn
