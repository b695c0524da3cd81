"""Program spans inside ``ServingEngine.step``, the AOT ledger's named
programs and the model's named scopes, on a tiny paged engine on the CPU.

The contract under test: every step opens ``serve.step`` and its children
(admission, chunk, growth, dispatch, device wait, sampling, emission,
telemetry) as profiler ``TraceAnnotation``s whose host-clock totals the
engine keeps; garbage-collection pauses count as ``py.gc``; every ledger
function compiles to an XLA module named ``jit_<ledger name>``; paged decode
attention and the paged KV writes carry the ``paged_attention`` and
``kv_write`` named scopes into the compiled HLO's metadata.
"""
import gc
import glob
import os

import jax
import pytest

from repro.configs import get_arch, reduced
from repro.serving.telemetry import SPANS

CHUNK = 8


@pytest.fixture(scope="module")
def served():
    """A warmed engine after serving three requests, one of them chunked,
    with no preemption."""
    from repro.models.api import build_model
    from repro.serving import EngineConfig, ServingEngine
    cfg = reduced(get_arch("llama3.2-1b"))
    api = build_model(cfg, max_seq=64)
    eng = ServingEngine(api, config=EngineConfig(
        num_slots=4, num_stages=1, num_microbatches=1, max_seq=64,
        prompt_capacity=32, page_size=4, prefill_chunk=CHUNK,
        seal_boundary=False, allow_swap=False),
        params=api.init(jax.random.PRNGKey(0)), backend="local")
    eng.warmup()
    for n in (5, 7, 2 * CHUNK + 3):
        eng.submit(list(range(1, n + 1)), 5)
    steps = 0
    while eng.scheduler.has_work():
        eng.step()
        steps += 1
    assert eng.preemptions == 0
    return eng, steps


def test_step_spans_count_and_nest(served):
    eng, steps = served
    tot = eng.telemetry.span_totals()
    assert set(SPANS) <= set(tot)
    step_s, step_n = tot["serve.step"]
    assert step_n == steps
    for name in SPANS[1:]:
        assert tot[name][0] <= step_s, name
    # the step's children that do not nest in one another
    parts = ("serve.admit", "serve.chunk", "serve.grow", "serve.dispatch",
             "serve.sample", "serve.emit", "serve.telemetry")
    assert sum(tot[n][0] for n in parts) <= step_s
    assert tot["serve.grow"][1] == steps
    # one dispatch and one device wait per decode call, plus one wait per
    # admission's first-token read (whole-prompt and chunked)
    decodes = eng.telemetry.steps_recorded
    assert tot["serve.dispatch"][1] == decodes > 0
    assert tot["serve.device_wait"][1] == decodes + eng.admissions
    assert tot["serve.admit"][1] == eng.admissions == 3
    assert tot["serve.chunk"][1] == eng.chunk_steps > 0
    assert tot["serve.sample"][1] == tot["serve.emit"][1] == decodes


def test_tok_per_s_counts_the_whole_step(served):
    eng, _ = served
    st = eng.stats()
    assert st["tok_per_s"] == pytest.approx(
        st["tokens_out"] / eng.telemetry.span_s["serve.step"])


def test_gc_pauses_are_spans(served):
    eng, _ = served
    n0 = eng.telemetry.span_totals()["py.gc"][1]
    gc.collect()
    s1, n1 = eng.telemetry.span_totals()["py.gc"]
    assert n1 == n0 + 1 and s1 > 0


def test_reset_zeroes_the_span_totals(served):
    from repro.serving.telemetry import StageTelemetry
    eng, _ = served
    tel = StageTelemetry(eng.replanner)
    with tel.span("serve.step", rid=3):
        pass
    assert tel.span_totals()["serve.step"][1] == 1
    tel.reset_measurements()
    assert all(v == (0.0, 0) for k, v in tel.span_totals().items()
               if k in SPANS)


def test_profiler_trace_carries_the_spans(served, tmp_path):
    """A CPU profiler trace of a few steps holds the program spans on its
    host plane, one ``serve.step`` per step run."""
    from jax.profiler import ProfileData
    eng, _ = served
    eng.submit(list(range(3, 9)), 3)
    jax.profiler.start_trace(str(tmp_path))
    steps = 0
    while eng.scheduler.has_work():
        eng.step()
        steps += 1
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    names = [e.name.split("#", 1)[0]
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    assert names.count("serve.step") == steps
    assert names.count("serve.dispatch") == names.count("serve.sample") > 0
    assert names.count("serve.admit") == 1


def test_ledger_programs_carry_their_names(served):
    eng, _ = served
    for name in ("decode_step", "prefill_chunk", "prefill_bucket", "insert",
                 "set_table_entry"):
        texts = [c.as_text() for c in eng.aot.fns[name].compiled.values()]
        assert texts, name
        assert all(t.startswith(f"HloModule jit_{name},") for t in texts)


def test_named_scopes_reach_the_compiled_hlo(served):
    eng, _ = served
    decode, = [c.as_text()
               for c in eng.aot.fns["decode_step"].compiled.values()]
    assert "/paged_attention/" in decode and "/kv_write/" in decode
    chunk = [c.as_text() for c in eng.aot.fns["prefill_chunk"].compiled
             .values()]
    assert all("/kv_write/" in t for t in chunk)
