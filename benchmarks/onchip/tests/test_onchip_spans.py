"""The reduction extended to the program's own spans (``trace_spans``):
self time, idle labelled by the running program or the innermost host
span, per-program and per-scope device time and the slowest engine steps,
on hand-made intervals and on two small traces recorded on a TPU v5e: a
jitted matmul between sleeps, and a tiny paged engine with its spans,
named programs and named scopes (``record_engine_trace.py``)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import pytest  # noqa: E402

import trace_reduce as TR  # noqa: E402
import trace_spans as S  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
ENGINE = os.path.join(DATA, "engine.xplane.pb")
ENGINE_SCOPES = os.path.join(DATA, "engine.scopes.json")


@pytest.mark.parametrize("path", [SMALL, ENGINE])
def test_busy_and_window_match_trace_reduce(path):
    """Busy time and the window are ``trace_reduce``'s, so the idle share
    reads the same whichever reduction the harness uses."""
    old = TR.reduce_file(path)
    new = S.reduce_file(path)
    assert new["busy_s"] == pytest.approx(old["busy_s"], rel=1e-12)
    assert new["window_s"] == pytest.approx(old["window_s"], rel=1e-12)
    assert sum(v for _, v in new["idle_gaps"]) == pytest.approx(
        new["window_s"] - new["busy_s"], rel=1e-9)


def test_trim_keeps_what_the_reductions_read(tmp_path):
    """The recorder's trimming leaves both reductions' readings as they
    were."""
    import record_engine_trace
    with open(SMALL, "rb") as f:
        space = f.read()
    path = tmp_path / "small.xplane.pb"
    path.write_bytes(record_engine_trace.trim(space))
    assert path.stat().st_size < len(space)
    assert TR.reduce_file(str(path)) == TR.reduce_file(SMALL)
    assert S.reduce_file(str(path)) == S.reduce_file(SMALL)


def test_hand_made_readings():
    """``trace_reduce``'s hand-made case: "add" starts later, so [25,30)
    is its own; each idle piece goes to the span covering it."""
    devices = {"/device:TPU:0": [("dot", 10, 30), ("add", 25, 40),
                                 ("dot", 60, 70)]}
    host = {TR.WINDOW_SPAN: [(0, 100)],
            "engine.step": [(5, 45), (55, 75)],
            "wait": [(45, 55), (75, 100)]}
    out = S.reduce(devices, host)
    assert out["busy_s"] == pytest.approx(40e-9)
    assert dict(out["device_ops"]) == {"dot": pytest.approx(25e-9),
                                       "add": pytest.approx(15e-9)}
    # [0,5) none, [5,10) engine.step; [40,45) engine.step, [45,55) wait,
    # [55,60) engine.step; [70,75) engine.step, [75,100) wait
    assert dict(out["idle_gaps"]) == {"engine.step": pytest.approx(20e-9),
                                      "wait": pytest.approx(35e-9),
                                      "other": pytest.approx(5e-9)}
    assert out["programs"] == out["scopes"] == out["slow_steps"] == []


def test_recorded_engine_trace():
    """A few steps of a tiny paged engine on one v5e: every program is
    named by its ledger name, the engine's spans sit under the harness's
    ``engine.step``, paged attention's ops carry their scope, and the
    idle time falls to the engine's spans and programs, not to
    ``engine.step``."""
    with open(ENGINE_SCOPES) as f:
        scopes = json.load(f)
    devices, host, programs = S.read(ENGINE, scopes)
    assert list(devices) == ["/device:TPU:0"]
    steps = len(host["engine.step"])
    for name in ("serve.step", "serve.grow", "serve.dispatch",
                 "serve.sample", "serve.emit", "serve.telemetry"):
        assert len(host[name]) == steps, name
    assert len(host["serve.admit"]) == 3
    assert len(host["serve.device_wait"]) == steps + 3
    out = S.reduce(devices, host, top=10_000, programs=programs)
    progs = {n: runs for n, _, runs in out["programs"]}
    assert progs["jit_decode_step"] == steps
    assert progs["jit_prefill_chunk"] == 3
    assert {"jit_prefill_bucket", "jit_insert", "jit_commit_slot",
            "jit_set_table_entry"} <= set(progs)
    assert not any("unknown" in n for n in progs)
    assert all(n.split("/")[0] in progs for n, _ in out["device_ops"])
    assert sum(v for _, v in out["device_ops"]) == pytest.approx(
        out["busy_s"])
    got = dict(out["scopes"])
    assert got["jit_decode_step/paged_attention"] > 0
    assert got["jit_decode_step/kv_write"] > 0
    assert got["jit_prefill_chunk/kv_write"] > 0
    gaps = dict(out["idle_gaps"])
    idle = out["window_s"] - out["busy_s"]
    assert gaps.get("engine.step", 0.0) < 0.1 * idle
    engine = sum(v for k, v in gaps.items()
                 if k.startswith(("serve.", "py.gc", "in:")))
    assert engine > 0.5 * idle
    assert len(out["slow_steps"]) == S.SLOW_STEPS


def test_innermost():
    items = [(0, 100, "loop"), (10, 40, "a"), (10, 20, "b"), (50, 60, "c"),
             (200, 210, "d")]
    assert S.innermost(items) == [(0, 10, "loop"), (10, 20, "b"),
                                  (20, 40, "a"), (40, 50, "loop"),
                                  (50, 60, "c"), (60, 100, "loop"),
                                  (200, 210, "d")]
    assert S.innermost([]) == []


def test_overlay():
    pieces = [(5, 15, "x"), (20, 30, "y")]
    got = list(S.overlay([(0, 10), (12, 25), (40, 50)], pieces))
    assert got == [(0, 5, None), (5, 10, "x"), (12, 15, "x"),
                   (15, 20, None), (20, 25, "y"), (40, 50, None)]


def test_self_time_sums_to_busy():
    """A loop's body operations take their time from the loop: the self
    times of one device sum to its busy time."""
    ops = [("p/while", 0, 100), ("p/fusion", 10, 40), ("p/fusion", 50, 60),
           ("p/copy", 120, 130)]
    out = S.reduce({"/device:TPU:0": ops}, {TR.WINDOW_SPAN: [(0, 200)]})
    got = dict(out["device_ops"])
    assert got == {"p/while": pytest.approx(60e-9),
                   "p/fusion": pytest.approx(40e-9),
                   "p/copy": pytest.approx(10e-9)}
    assert sum(got.values()) == pytest.approx(out["busy_s"])


def test_idle_in_programs_and_innermost_spans():
    """Idle inside a running program is the program's; the rest goes to
    the innermost host span covering each piece."""
    ops = [("jit_decode_step/fusion", 100, 110),
           ("jit_decode_step/fusion", 150, 160),
           ("jit_prefill_chunk/fusion", 20, 30)]
    programs = {"/device:TPU:0": [("jit_prefill_chunk", 20, 40),
                                  ("jit_decode_step", 100, 160)]}
    host = {TR.WINDOW_SPAN: [(0, 200)],
            "engine.step": [(0, 190)],
            "serve.step": [(5, 185)],
            "serve.chunk": [(10, 45)],
            "py.gc": [(50, 60)],
            "serve.device_wait": [(60, 165)],
            "wait": [(190, 200)]}
    out = S.reduce({"/device:TPU:0": ops}, host, programs=programs)
    gaps = dict(out["idle_gaps"])
    assert gaps == {
        "engine.step": pytest.approx(10e-9),     # [0,5), [185,190)
        "serve.step": pytest.approx(30e-9),      # [5,10), [45,50), [165,185)
        "serve.chunk": pytest.approx(15e-9),     # [10,20), [40,45)
        "in:jit_prefill_chunk": pytest.approx(10e-9),   # [30,40)
        "py.gc": pytest.approx(10e-9),           # [50,60)
        "serve.device_wait": pytest.approx(45e-9),  # [60,100), [160,165)
        "in:jit_decode_step": pytest.approx(40e-9),     # [110,150)
        "wait": pytest.approx(10e-9)}
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])
    progs = {n: (t, k) for n, t, k in out["programs"]}
    assert progs == {"jit_decode_step": (pytest.approx(60e-9), 1),
                     "jit_prefill_chunk": (pytest.approx(20e-9), 1)}
    # the one engine step, broken down by its spans' self time and the
    # programs run inside it
    (dur, spans, progs), = out["slow_steps"]
    assert dur == pytest.approx(180e-9)
    spans = dict(spans)
    assert spans["serve.chunk"] == pytest.approx(35e-9)
    assert spans["serve.device_wait"] == pytest.approx(105e-9)
    assert sum(spans.values()) == pytest.approx(dur)
    assert dict(progs) == {"jit_decode_step": pytest.approx(60e-9),
                           "jit_prefill_chunk": pytest.approx(20e-9)}


def test_scope_time_per_program():
    ops = [S.Op("jit_decode_step/while", 0, 100),
           S.Op("jit_decode_step/fusion", 10, 30, "paged_attention"),
           S.Op("jit_decode_step/fusion", 40, 50, "kv_write"),
           S.Op("jit_decode_step/fusion", 60, 70, "paged_attention"),
           S.Op("jit_prefill_chunk/fusion", 120, 130, "kv_write")]
    out = S.reduce({"/device:TPU:0": ops}, {TR.WINDOW_SPAN: [(0, 200)]})
    assert dict(out["scopes"]) == {
        "jit_decode_step/paged_attention": pytest.approx(30e-9),
        "jit_decode_step/kv_write": pytest.approx(10e-9),
        "jit_prefill_chunk/kv_write": pytest.approx(10e-9)}


def test_scopes_from_compiled_hlo():
    """A named scope in the program reaches the compiled HLO's op_name
    metadata, keyed by the module's name and the instruction's."""
    import jax
    import jax.numpy as jnp

    def attend(x, w):
        with jax.named_scope("paged_attention"):
            y = jnp.tanh(x @ w)
        return y + 1.0

    attend.__name__ = "decode_step"
    text = jax.jit(attend).lower(jnp.ones((4, 4)),
                                 jnp.ones((4, 4))).compile().as_text()
    got = S.scopes_from_hlo([text, "not an HLO module"])
    assert list(got) == ["jit_decode_step"]
    assert set(got["jit_decode_step"].values()) == {"paged_attention"}
    for inst in got["jit_decode_step"]:
        assert f"%{inst} = " in text
