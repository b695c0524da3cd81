"""The harness end to end on the CPU at a tiny size, through its library
functions: a cell defined only by data files, the comparison with the
plain reference, its control, and a fault planted in the served path. The
command itself refuses to run without a TPU."""

import json
import os
import re
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import harness  # noqa: E402
import traffic  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
SEED = 2**31 + 12345
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_bench():
    """BENCHMARK.json with a cell that exists only as data files under
    tests/data: a configuration and a mix."""
    bench = harness.load_bench()
    bench["configs"] = [{"name": "tiny", "file": os.path.relpath(
        os.path.join(DATA, "tiny.json"), harness.REPO)}]
    bench["workloads"] = [
        {"name": "tiny.chat", "config": "tiny", "traffic": "tiny-chat",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    return bench


def run_tiny(name, trace=False, keep=None, seconds=3.0):
    cell = harness.find_cell(tiny_bench(), name, traffic_dir=DATA)
    devices = jax.devices()[:1]
    out = harness.run(cell, SEED, seconds, trace, time.time(),
                      devices=devices, peaks=PEAKS, keep=keep,
                      log=lambda s: None)
    return cell, out, harness.result(cell, out, trace, devices)


@pytest.fixture(scope="module")
def chat():
    keep = {}
    cell, out, line = run_tiny("tiny.chat", keep=keep)
    return cell, out, line, keep


def test_data_only_cell_runs_and_is_correct(chat):
    cell, out, line, _ = chat
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    tr = traffic.generate(cell.mix, SEED, 3.0,
                          cell.config["padded_vocab_size"])
    assert line["attempted"] == len(tr.requests) and line["failed"] == 0
    for spec in harness.load_bench()["end_to_end"]:
        assert line["metrics"][spec["name"]]["value"] > 0, spec["name"]
    # the sweep's readers, outside the cells' metrics
    for name in ("ttft_p50_ms", "queue_wait_p90_ms"):
        assert harness.reader(name)(out.run) > 0, name
    assert line["device"]["count"] == 1
    json.dumps(line)


def test_per_layer_readers(chat):
    cell, out, _, _ = chat
    line = harness.result(cell, out, True, jax.devices()[:1])
    got = line["metrics"]
    assert got["window_compiles"]["value"] == 0
    assert 0 < got["prefix_hit_share"]["value"] < 100
    assert 0 < got["step_mfu"]["value"] < 100
    assert got["decode_step_ms"]["value"] > 0
    # no trace was taken: the device metric finds nothing and is left out
    assert "device_idle_share" not in got


def test_control_fails_the_limit(chat):
    """The fp8 control on the same prompts and served tokens reads far
    above the program and above the limit."""
    cell, out, _, keep = chat
    pairs = [(list(t.req.prompt), list(t.req.generated))
             for t in keep["sample"]]
    cap = cell.mix["engine"]["request_capacity"]
    ref = harness.reference_of(cell)
    ctl = np.concatenate(ref.control_gaps(keep["params"], keep["m"], cap,
                                          pairs))
    prog = out.checks["max_logit_gap"]["value"]
    limit = out.checks["max_logit_gap"]["limit"]
    assert prog <= limit < ctl.max()
    assert ctl.max() >= 3 * prog


def test_altered_token_is_caught(monkeypatch):
    """A token altered where the engine samples it makes the run
    incorrect."""
    from repro.serving import sampling
    real = sampling.TokenSampler.sample
    calls = {"n": 0}

    def altered(self, logits, rids, indices):
        toks = real(self, logits, rids, indices)
        calls["n"] += 1
        if calls["n"] % 7 == 0:
            toks = (toks + 1) % logits.shape[-1]
        return toks

    monkeypatch.setattr(sampling.TokenSampler, "sample", altered)
    _, out, line = run_tiny("tiny.chat")
    assert line["correct"] is False
    assert out.checks["max_logit_gap"]["value"] > \
        out.checks["max_logit_gap"]["limit"]


def test_warm_start_is_served_and_not_measured(chat):
    """The warm requests enter at the lead-in's start, are served, and
    count in no request metric; the window reports its steadiness."""
    cell, out, _, _ = chat
    lead_in = cell.mix["lead_in_s"]
    warm = [t for t in out.run.tracks if t.due <= -lead_in]
    assert warm and not any(t.in_window for t in warm)
    assert all(t.times for t in warm)
    tr = traffic.generate(cell.mix, SEED, 3.0,
                          cell.config["padded_vocab_size"])
    st = harness.steadiness(out.run, tr)
    assert st["served_tok_s"] > 0 and st["offered_tok_s"] > 0
    assert st["ttft_p50_ms_first_half"] > 0


def test_command_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"),
         "--workload", "glm4-9b-s20.chat", "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_is_whole():
    """Every name is well formed and every file the spec names exists."""
    b = harness.load_bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks/onchip"]
    assert 1 <= b["run_seconds"] <= 51
    names = [c["name"] for c in b["configs"]]
    for c in b["configs"]:
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        data = harness.load_json(os.path.join(harness.REPO, c["file"]))
        assert data["name"] == c["name"] and data["source"] == c["source"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert data[k] < data["published"][k]
        for module in (data["weights"], data["reference"]):
            assert os.path.exists(os.path.join(harness.HERE, module + ".py"))
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           m["name"] + ".py"))
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    cells = [w["name"] for w in b["workloads"]]
    assert len(set(cells)) == len(cells)
    for w in b["workloads"]:
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cell = harness.find_cell(b, w["name"])
        assert cell.end_to_end and cell.per_layer
    for m in metrics:
        for w in m.get("workloads", []):
            assert w in cells


def test_reference_takes_an_unsealed_plan_and_refuses_a_sealed_one(chat):
    """Without a plan, or with an unsealed one, the reference gives the
    gaps the run compared; a sealed plan is refused by name."""
    cell, out, _, keep = chat
    pairs = [(list(t.req.prompt), list(t.req.generated))
             for t in keep["sample"]]
    cap = cell.mix["engine"]["request_capacity"]
    ref = harness.reference_of(cell)
    plain = ref.served_gaps(keep["params"], keep["m"], cap, pairs)
    assert max(g.max() for g in plain) == \
        out.checks["max_logit_gap"]["value"]
    for plan in (None, {"stage_blocks": (2,), "seal_boundary": False}):
        got = ref.served_gaps(keep["params"], keep["m"], cap, pairs,
                              plan=plan)
        for a, b in zip(got, plain):
            np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="rounds at the same cuts"):
        ref.served_gaps(keep["params"], keep["m"], cap, pairs,
                        plan={"stage_blocks": (1, 1), "seal_boundary": True})


def test_spans_and_stats_reach_the_counters(chat):
    """Every program span and numeric stat of the engine reaches the run's
    counters as a window delta, one first seen inside the window
    included."""
    cell, out, _, keep = chat
    c = out.run.counters
    assert c["span_s.serve.step"] > c["span_s.serve.device_wait"] > 0
    assert c["span_n.serve.dispatch"] == c["decode_steps"] > 0
    assert c["stat.tokens_out"] > 0 and "stat.backend" not in c
    assert harness.reader("host_ms_per_step")(out.run) > 0
    eng = harness.build_engine(cell, keep["params"], keep["m"])
    c0 = harness.counters(eng)
    with eng.telemetry.span("bench.test_only"):
        pass
    delta = harness.window_delta(c0, harness.counters(eng))
    assert "span_n.bench.test_only" not in c0
    assert delta["span_n.bench.test_only"] == 1
    assert delta["span_s.bench.test_only"] > 0
    assert delta["stat.steps"] == 0 and delta["compiles"] == 0
