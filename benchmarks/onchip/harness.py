"""Drive one cell of the benchmark: build the cell's model and engine, serve
its traffic for a measured window, check what was served against the plain
reference, and reduce what was recorded to the cell's metrics.

Everything that belongs to one configuration, traffic mix or metric is data
or a file of its own, found by the name in ``BENCHMARK.json``:

* ``configs/<config>.json``: sizes (in the source's own key names), the map
  from them to the program's architecture fields, the modules that draw
  its weights, hold its plain reference and count its work (``costs``, by
  default ``costs``; each ``<module>.py`` here), the KV pool's bytes over
  all layers and page size, and optionally how the cell is served
  (``serve``: ``backend``, ``local`` or ``pipelined``, and fields of the
  engine's ``EngineConfig``; see ``serving_plan``);
* ``traffic/<mix>.json``: the mix (see ``traffic.py``; its arrival process
  is a module ``arrivals/<process>.py``), the engine's shapes and the
  correctness limits of the cell;
* ``metrics/<metric>.py``: a reader ``read(run) -> float | None`` of a
  ``Run`` record; ``None`` means there was nothing to read. A run records
  every span and numeric stat of the engine (``counters``) and, traced,
  each program's device time and each named scope's self time
  (``reduce_trace``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
SRC = os.path.join(REPO, "src")
CACHE_DIR = os.path.join(HERE, ".cache", "jax")
RUN_DIR = os.path.join(HERE, ".cache", "runs")

if SRC not in sys.path:
    sys.path.insert(0, SRC)

import jax  # noqa: E402
import numpy as np  # noqa: E402

import traffic as traffic_gen  # noqa: E402
from peaks import peaks_for  # noqa: E402


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


# ---------------------------------------------------------------------------
# The spec: BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------
def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_bench(path: Optional[str] = None) -> Dict:
    return load_json(path or os.path.join(REPO, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict          # configs/<config>.json
    mix: Dict             # traffic/<mix>.json
    end_to_end: List[Dict]
    per_layer: List[Dict]
    backend: str          # "local" or "pipelined"
    engine: Dict          # EngineConfig fields but the pool's pages


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bench: Dict, name: str, root: str = REPO,
              traffic_dir: str = os.path.join(HERE, "traffic")) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics."""
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    mix = load_json(os.path.join(traffic_dir, w["traffic"] + ".json"))
    backend, engine = serving_plan(name, w["chips"], config, mix)
    return Cell(name, w["chips"], config, mix,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)],
                backend, engine)


# the engine every cell gets; a configuration's "serve" changes fields of it
ENGINE = dict(num_stages=1, num_microbatches=1, page_policy="demand",
              preempt_policy="swap", prefix_sharing=True)
# fields the mix and the configuration's pool set, which "serve" may not
SIZED_ELSEWHERE = ("num_slots", "prompt_capacity", "request_capacity",
                   "prefill_chunk", "page_size", "num_pages")


def serving_plan(name: str, chips: int, config: Dict, mix: Dict):
    """The backend and the ``EngineConfig`` fields (the pool's pages left
    to ``build_engine``) that serve cell ``name``. Without ``serve`` in the
    configuration: the local backend on one stage. A plan whose stages do
    not match the cell's chips is refused here, before anything is built."""
    from repro.serving import EngineConfig
    serve = dict(config.get("serve", {}))
    backend = serve.pop("backend", "local")
    known = {f.name for f in dataclasses.fields(EngineConfig)}
    bad = sorted(k for k in serve if k not in known or k in SIZED_ELSEWHERE)
    if backend not in ("local", "pipelined") or bad:
        raise ValueError(f"cell {name!r}: serve has backend {backend!r} "
                         f"and fields it may not set: {bad}")
    eng_mix = mix["engine"]
    fields = dict(ENGINE, **serve,
                  **{k: eng_mix[k] for k in ("num_slots", "prompt_capacity",
                                              "request_capacity",
                                              "prefill_chunk")},
                  page_size=config["page_size"])
    if fields["num_stages"] != chips:
        raise ValueError(f"cell {name!r}: serve.num_stages "
                         f"{fields['num_stages']} differs from the cell's "
                         f"{chips} chips")
    return backend, fields


def model_dims(config: Dict) -> Dict[str, Any]:
    """The configuration in the program's field names."""
    return {field: config[key]
            for field, key in config["arch"]["fields"].items()}


def reader(name: str) -> Callable:
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def weights_of(cell: Cell):
    """The module that draws the configuration's weights:
    ``init(m, seed, mesh=None)``."""
    return importlib.import_module(cell.config["weights"])


def costs_of(cell: Cell):
    """The module that counts the configuration's needed work."""
    return importlib.import_module(cell.config.get("costs", "costs"))


def reference_of(cell: Cell):
    """The module that holds the configuration's plain reference."""
    return importlib.import_module(cell.config["reference"])


# ---------------------------------------------------------------------------
# Set-up: device, compile cache, weights, engine
# ---------------------------------------------------------------------------
def check_devices(chips: int) -> List[Any]:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devs)}")
    peaks_for(devs[0].device_kind)        # an unknown kind is an error
    return devs[:chips]


def use_compile_cache(path: str = CACHE_DIR) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment names: only the checkout outlasts a
    run, and the path is part of the cache's key."""
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def serving_mesh(cell: Cell, devices):
    """The ``(chips, 1)`` ``("pod", "data")`` mesh over ``devices`` that a
    pipelined cell is served on; None for the local backend."""
    if cell.backend != "pipelined":
        return None
    from jax.sharding import AxisType
    return jax.make_mesh((cell.chips, 1), ("pod", "data"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=list(devices))


def build_engine(cell: Cell, params, m: Dict, mesh=None):
    from repro.configs import get_arch
    from repro.models.api import build_model
    from repro.serving import EngineConfig, ServingEngine

    cfg = dataclasses.replace(get_arch(cell.config["arch"]["program_arch"]),
                              **m)
    eng_mix = cell.mix["engine"]
    api = build_model(cfg, max_seq=eng_mix["request_capacity"])
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)),
                        api.abstract_params())
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    assert want == got, "drawn weights do not match the program's layout"
    page_bytes = cell.config["page_size"] \
        * costs_of(cell).kv_bytes_per_token(m)
    num_pages = cell.config["kv_pool_bytes"] // page_bytes + 1  # + null page
    ec = EngineConfig(num_pages=int(num_pages), **cell.engine)
    return ServingEngine(api, mesh=mesh, config=ec, params=params,
                         backend=cell.backend)


# ---------------------------------------------------------------------------
# The measured window
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Track:
    """What the client saw of one request (host clock, seconds from the
    window's start)."""
    req: Any                      # the engine's Request
    due: float
    prompt_len: int
    in_window: bool = True        # due inside the window
    admit: Optional[float] = None  # first admission (queue left)
    shared_tokens: Optional[int] = None
    times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: str
    seconds: float
    setup_s: float
    tracks: List[Track]
    counters: Dict[str, float]    # deltas over the window
    flops: float                  # needed work inside the window
    bytes: float
    peaks: Dict[str, float]       # of one chip
    chips: int = 1
    trace: Optional[Dict] = None  # reduce_trace() of the traced part
    traced_attn_bytes: float = 0.0  # paged attention's, traced steps

    def in_window(self, t: float) -> bool:
        return 0.0 <= t <= self.seconds

    @property
    def tokens(self) -> int:
        """Output tokens emitted inside the window."""
        return sum(sum(1 for t in tr.times if self.in_window(t))
                   for tr in self.tracks)


class Window:
    """Drives ``ServingEngine.submit`` and ``step`` through the window and
    records, after every step, each request's new tokens on the host clock,
    its admission, and the work each step did."""

    def __init__(self, eng, cell: Cell, m: Dict, clock=time.perf_counter):
        self.eng, self.cell, self.m, self.clock = eng, cell, m, clock
        self.costs = costs_of(cell)
        self.Pg = cell.config["page_size"]
        self.live: Dict[int, Track] = {}      # rid -> track, not finished
        self.tracks: List[Track] = []
        self.flops = 0.0
        self.bytes = 0.0
        self.t0 = 0.0
        self.tracing = False                  # the profiler traces
        self.traced_attn_bytes = 0.0
        self.late: List[float] = []           # generator lateness, seconds
        self._chunk_pos: Dict[int, int] = {}

    def submit(self, r, due: float, in_window: bool = True) -> Track:
        req = self.eng.submit(r.prompt, r.max_new)
        tr = Track(req, due, len(r.prompt), in_window)
        self.tracks.append(tr)
        self.live[req.rid] = tr
        return tr

    def step(self, account: bool = True) -> None:
        """One engine step."""
        events = self.eng.step()
        now = self.clock() - self.t0
        firsts: Dict[int, int] = {}
        for ev in events:
            d = ev.detail if isinstance(ev.detail, dict) else {}
            tr = self.live.get(d.get("rid"))
            if ev.kind in ("admit", "chunk_admit") and tr is not None \
                    and tr.admit is None:
                tr.admit = now
            if ev.kind == "admit" and tr is not None:
                if d.get("resumed") != "swap":
                    firsts[tr.req.rid] = firsts.get(tr.req.rid, 0) + 1
                if "resumed" not in d and "resumed_at" not in d \
                        and tr.shared_tokens is None:
                    tr.shared_tokens = min(d.get("shared", 0) * self.Pg,
                                           tr.prompt_len)
                if account:
                    self._account_admit(tr, d)
            elif ev.kind == "chunk" and account and tr is not None:
                self._account_chunk(d)
            elif ev.kind == "preempt" and d.get("mid_prefill"):
                self._chunk_pos.pop(d.get("rid"), None)
        contexts = []
        for rid, tr in list(self.live.items()):
            n = len(tr.req.generated)
            new = n - len(tr.times)
            if new > 0:
                tr.times.extend([now] * new)
                # tokens beyond the admission's first one came from this
                # step's decode tick; its input sat at position P + n - 2
                for _ in range(new - firsts.get(rid, 0)):
                    contexts.append(tr.prompt_len + n - 1)
            if tr.req.status == "done":
                del self.live[rid]
        if account and contexts:
            self.flops += self.costs.decode_flops(self.m, contexts)
            self.bytes += self.costs.decode_bytes(self.m, contexts)
            if self.tracing:
                self.traced_attn_bytes += self.costs.paged_attention_bytes(
                    self.m, contexts)

    def _account_admit(self, tr: Track, d: Dict) -> None:
        """The prefill an admission needed: the prompt's tokens past the
        pages it adopted (a swap-in restores pages and computes nothing)."""
        if d.get("resumed") == "swap":
            return
        P = tr.prompt_len + d.get("resumed_at", 0)
        sh = min(d.get("shared", 0) * self.Pg, P)
        self.flops += self.costs.prefill_flops(self.m, sh, P - sh)
        if "chunks" not in d:       # a chunked prompt's bytes go per chunk
            self.bytes += self.costs.prefill_bytes(self.m, sh, P - sh)

    def _account_chunk(self, d: Dict) -> None:
        rid, end = d["rid"], d["pos"]
        start = self._chunk_pos.get(rid, 0)
        if end <= start:
            start = 0
        self._chunk_pos[rid] = end
        self.bytes += self.costs.prefill_bytes(self.m, start, end - start)


def _sleep_until(clock, t: float) -> None:
    while True:
        dt = t - clock()
        if dt <= 0:
            return
        time.sleep(min(dt, 0.002) if dt < 0.01 else dt - 0.005)


def counters(eng) -> Dict[str, float]:
    """The engine's running totals: a few named ones, the seconds and
    count of every program span (``span_s.<name>``, ``span_n.<name>``) and
    every numeric top-level entry of ``stats()`` (``stat.<key>``)."""
    st = eng.stats()
    out = {"preemptions": st.get("preemptions", 0),
           "swap_outs": st.get("swap_outs", 0),
           "swap_ins": st.get("swap_ins", 0),
           "compiles": eng.aot.post_freeze_compiles or 0,
           "decode_wall_s": eng.telemetry.wall_s,
           "decode_steps": eng.telemetry.steps_recorded,
           "failed": len(eng.failed)}
    for name, (sec, n) in eng.telemetry.span_totals().items():
        out[f"span_s.{name}"], out[f"span_n.{name}"] = sec, n
    out.update((f"stat.{k}", v) for k, v in st.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool))
    return out


def window_delta(c0: Dict[str, float], c1: Dict[str, float]
                 ) -> Dict[str, float]:
    """Each counter's growth over the window; one first seen inside it
    started at 0."""
    return {k: v - c0.get(k, 0) for k, v in c1.items()}


def serve_window(win: Window, tr_gen, seconds: float, lead_in: float,
                 trace_dir: Optional[str], trace_s: float, log) -> Dict:
    """The measured window. Each request is submitted at its due time and
    timed from it; the warm requests are submitted at once, and they and
    the requests due in the first ``lead_in`` seconds bring the engine to
    its steady state before the window opens and are not measured. Returns
    the engine's counters' deltas over the window."""
    eng, clock = win.eng, win.clock
    span = jax.profiler.TraceAnnotation if trace_dir is not None \
        else (lambda name: contextlib.nullcontext())
    pending = list(tr_gen.requests)
    k = 0
    traced = None
    t_traffic = clock()
    win.t0 = t_traffic + lead_in
    c0 = counters(eng) if lead_in <= 0 else None
    t_end = win.t0 + seconds
    t_trace = t_end - trace_s
    while True:
        now = clock()
        if now >= t_end:
            break
        if c0 is None and now >= win.t0:
            c0 = counters(eng)
        if trace_dir is not None and traced is None and now >= t_trace:
            jax.profiler.start_trace(trace_dir)
            traced = jax.profiler.TraceAnnotation("bench.window")
            traced.__enter__()
            win.tracing = True
        with span("submit"):
            while k < len(pending) and t_traffic + pending[k].due_s <= now:
                r = pending[k]
                if not r.warm:
                    win.late.append(now - t_traffic - r.due_s)
                win.submit(r, r.due_s - lead_in,
                           in_window=not r.warm and r.due_s >= lead_in)
                k += 1
        if eng.scheduler.has_work() and not eng.stalled:
            with span("engine.step"):
                win.step(account=c0 is not None)
        else:
            nxt_due = t_traffic + pending[k].due_s if k < len(pending) \
                else t_end
            with span("wait"):
                _sleep_until(clock, min(nxt_due, t_end, now + 0.05))
    if traced is not None:
        win.tracing = False
        traced.__exit__(None, None, None)
        jax.profiler.stop_trace()
    c1 = counters(eng)
    if win.late:
        late = np.asarray(win.late) * 1e3
        log(f"generator lateness: p50 {np.percentile(late, 50):.3f} ms, "
            f"p99 {np.percentile(late, 99):.3f} ms, max {late.max():.3f} ms "
            f"over {len(late)} submissions")
    return window_delta(c0 or c1, c1)


def steadiness(run: "Run", tr_gen) -> Dict[str, float]:
    """Whether the window saw a steady state: the output tokens served
    against those the mix offers (rate x mean reply), and the median TTFT
    of the window's first and second halves (a growing backlog shows as
    the second above the first)."""
    halves = [[], []]
    for tr in run.tracks:
        if tr.in_window:
            first = tr.times[0] if tr.times else run.seconds
            halves[int(tr.due >= run.seconds / 2)].append(
                min(first, run.seconds) - tr.due)
    med = [float(np.median(h)) * 1e3 if h else math.nan for h in halves]
    return {"served_tok_s": run.tokens / run.seconds,
            "offered_tok_s": tr_gen.offered_tok_s,
            "ttft_p50_ms_first_half": med[0],
            "ttft_p50_ms_second_half": med[1]}


# ---------------------------------------------------------------------------
# Correctness: the served tokens against the plain reference
# ---------------------------------------------------------------------------
def pick_sample(tracks: List[Track], seed: int, tokens: int,
                chunk: int) -> List[Track]:
    """Finished requests drawn from the seed until they hold ``tokens``
    served tokens, with the longest in it, the most preempted one, and one
    prompt of each prefill path (whole prompt, chunked) where the window
    finished one."""
    done = [t for t in tracks if t.req.status == "done"]
    if not done:
        return []
    rng = np.random.default_rng([3, seed % 2**63])
    order = [done[i] for i in rng.permutation(len(done))]
    pick = [max(done, key=lambda t: t.prompt_len + len(t.req.generated)),
            max(done, key=lambda t: t.req.preemptions)]
    pick += [next((t for t in order if t.prompt_len <= chunk), pick[0]),
             next((t for t in order if t.prompt_len > chunk), pick[0])]
    pick = list({id(t): t for t in pick}.values())
    for t in order:
        if sum(len(p.req.generated) for p in pick) >= tokens:
            break
        if t not in pick:
            pick.append(t)
    return pick


def check(params, m: Dict, cell: Cell, sample: List[Track],
          plan: Optional[Dict] = None) -> Dict[str, Dict]:
    """The numbers compared, each with its limit. ``plan`` (how the engine
    served, for a configuration with ``serve``) goes to the reference."""
    lim = cell.mix["check"]
    pairs = [(list(t.req.prompt), list(t.req.generated)) for t in sample]
    cap = cell.mix["engine"]["request_capacity"]
    ref = reference_of(cell)
    gaps = ref.served_gaps(params, m, cap, pairs) if plan is None \
        else ref.served_gaps(params, m, cap, pairs, plan=plan)
    served = int(sum(len(g) for g in gaps))
    widest = float(max((g.max() for g in gaps if len(g)), default=math.inf))
    return {
        "max_logit_gap": {"value": widest, "limit": lim["max_logit_gap"]},
        "served_tokens_compared": {"value": served,
                                   "limit": lim["min_tokens_compared"]},
    }


def is_correct(checks: Dict[str, Dict]) -> bool:
    """Every number within its limit: the widest gap at most its limit,
    at least the least number of served tokens compared, no request
    failed."""
    return (checks["max_logit_gap"]["value"]
            <= checks["max_logit_gap"]["limit"]
            and checks["served_tokens_compared"]["value"]
            >= checks["served_tokens_compared"]["limit"]
            and checks["failed_requests"]["value"]
            <= checks["failed_requests"]["limit"])


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Outcome:
    run: Run
    checks: Dict[str, Dict]
    attempted: int
    failed: int
    memory_peak_bytes: int


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_process: float,
        *, devices, peaks: Dict[str, float], log=None,
        keep: Optional[Dict] = None) -> Outcome:
    """One run of ``cell`` on ``devices`` (the chips the caller checked);
    ``keep`` receives the sample and the weights."""
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    m = model_dims(cell.config)
    mesh = serving_mesh(cell, devices)
    params = weights_of(cell).init(m, seed, mesh=mesh)
    jax.block_until_ready(params)
    log(f"weights drawn: {time.time() - t_process:.1f} s since start")
    eng = build_engine(cell, params, m, mesh)
    t0 = time.perf_counter()
    eng.warmup()
    log(f"engine: {eng.pool.num_pages} pages x {eng.config.page_size}, "
        f"{eng.pages_per_slot} pages/slot; warmup "
        f"{time.perf_counter() - t0:.1f} s")
    tr_gen = traffic_gen.generate(cell.mix, seed, seconds, m["vocab_size"])
    log(f"traffic: {traffic_gen.describe(tr_gen)}")
    win = Window(eng, cell, m)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(RUN_DIR, f"trace-{os.getpid()}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    lead_in = cell.mix.get("lead_in_s", 0.0)
    setup_s = time.time() - t_process + lead_in
    delta = serve_window(win, tr_gen, seconds, lead_in, trace_dir,
                         min(10.0, seconds / 2), log)
    log(f"window deltas: {delta}")
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    tracks = win.tracks
    attempted = len(tracks)
    rec = Run(cell.name, seconds, setup_s, tracks, delta, win.flops,
              win.bytes, peaks, chips=len(devices),
              traced_attn_bytes=win.traced_attn_bytes)
    log(f"steady state: {steadiness(rec, tr_gen)}")
    sample = pick_sample(tracks, seed, cell.mix["check"]["sample_tokens"],
                         cell.mix["engine"]["prefill_chunk"])
    plan = None
    if "serve" in cell.config:
        plan = {"stage_blocks": tuple(eng.stage_blocks),
                "seal_boundary": eng.backend_kind == "pipelined"
                and eng.config.seal_boundary}
    if trace_dir is not None:
        import trace_spans
        scopes = trace_spans.program_scopes(eng)
    # the engine's state goes before the reference runs beside the weights
    del win, eng
    gc.collect()
    t_ref = time.perf_counter()
    checks = check(params, m, cell, sample, plan)
    checks["failed_requests"] = {"value": int(delta["failed"]), "limit": 0}
    log(f"reference over {len(sample)} requests: "
        f"{time.perf_counter() - t_ref:.1f} s")
    if keep is not None:
        keep.update(sample=sample, params=params, m=m)
    if trace_dir is not None:
        import trace_reduce
        t_tr = time.perf_counter()
        try:
            rec.trace = reduce_trace(trace_reduce.find_xplane(trace_dir),
                                     scopes)
        except (FileNotFoundError, ValueError) as e:
            log(f"trace not reduced: {e}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t_tr:.1f} s")
    return Outcome(rec, checks, attempted, int(delta["failed"]), peak)


def reduce_trace(path: str, scopes: Dict[str, Dict[str, str]]) -> Dict:
    """``trace_reduce``'s busy time, window, top operations and idle gaps,
    which the result line and ``device_idle_share`` read, with each
    program's device seconds and runs (``programs``: ``[name, seconds,
    runs]``) and each named scope's device self seconds per program
    (``scopes``: ``[<program>/<scope>, seconds]``) from ``trace_spans``;
    seconds are means over the device planes (``devices``)."""
    import trace_reduce
    import trace_spans
    devices, host, programs = trace_spans.read(path, scopes)
    out = trace_reduce.reduce({d: [op[:3] for op in ops]
                               for d, ops in devices.items()}, host)
    spans = trace_spans.reduce(devices, host, programs=programs)
    out["programs"], out["scopes"] = spans["programs"], spans["scopes"]
    return out


def result(cell: Cell, out: Outcome, trace: bool, devices) -> Dict:
    """The result line: the cell's end-to-end metrics, or with ``trace``
    its per-layer metrics; the numbers compared come last."""
    metrics = {}
    for spec in (cell.per_layer if trace else cell.end_to_end):
        v = reader(spec["name"])(out.run)
        if v is not None:
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": is_correct(out.checks),
            "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    if trace and out.run.trace is not None:
        device["busy_s"] = out.run.trace["busy_s"]
        device["window_s"] = out.run.trace["window_s"]
        line["breakdown"] = {"device_ops": out.run.trace["device_ops"],
                             "idle_gaps": out.run.trace["idle_gaps"]}
    line["checks"] = out.checks
    return line
