"""How a configuration file says its cell is served (``serve``), on the CPU:
a four-stage pipelined cell defined only by data files, served through
``harness.run`` on four virtual devices with its weights drawn sharded by
layer; the refusal of a plan that does not fit the cell; and the one-chip
cells left as they were (engine, weights, the reference without a plan,
the shares of a peak, the trace's readings)."""

import json
import math
import os
import subprocess
import sys
import textwrap

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import dense_weights  # noqa: E402
import harness  # noqa: E402
import trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
SEED = 2**31 + 12345


def bench_with(config: str, chips: int):
    """BENCHMARK.json with one cell ``<config>.chat``: the configuration
    ``tests/data/<config>.json`` under the tiny chat mix on ``chips``."""
    bench = harness.load_bench()
    bench["configs"] = [{"name": config, "file": os.path.relpath(
        os.path.join(DATA, config + ".json"), harness.REPO)}]
    bench["workloads"] = [{"name": f"{config}.chat", "config": config,
                           "traffic": "tiny-chat", "chips": chips,
                           "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    return bench


def run_on_devices(code: str, devices: int = 4, timeout: int = 600) -> str:
    """``code`` in a fresh process that sees ``devices`` virtual CPU
    devices (this process keeps its one)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.pathsep.join(
        [harness.SRC, harness.HERE, HERE, env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)
    assert proc.returncode == 0, (proc.stdout[-4000:], proc.stderr[-4000:])
    return proc.stdout


def test_pipelined_cell_from_data_files():
    """``tiny-p4``: four layers, ``serve`` pipelined over four stages with
    the seal off, served through ``harness.run`` on four devices and
    checked against the plain reference at tiny's own limit. The block
    weights sit one layer per device; each device holds its stage's blocks
    and the replicated embedding, final norm and head, and no more."""
    out = run_on_devices("""
        import json, time
        import jax
        import harness
        from test_onchip_serve_plan import bench_with, DATA, SEED
        cell = harness.find_cell(bench_with("tiny-p4", 4), "tiny-p4.chat",
                                 traffic_dir=DATA)
        seen = {}
        build = harness.build_engine
        def spy(*a, **kw):
            eng = build(*a, **kw)
            seen.update(backend=eng.backend_kind,
                        stages=eng.config.num_stages,
                        blocks=list(eng.stage_blocks),
                        seal=eng.config.seal_boundary)
            return eng
        harness.build_engine = spy
        devices = jax.devices()[:4]
        keep = {}
        out = harness.run(cell, SEED, 3.0, False, time.time(),
                          devices=devices, peaks={"bf16_flops_per_s": 1e12,
                          "hbm_bytes_per_s": 1e11}, keep=keep,
                          log=lambda s: None)
        line = harness.result(cell, out, False, devices)
        params = keep["params"]
        shards = {}
        for path, a in jax.tree_util.tree_leaves_with_path(params):
            top = path[0].key
            for sh in a.addressable_shards:
                shards.setdefault(sh.device.id, []).append(
                    [top, [s.start for s in sh.index[:1]],
                     int(sh.data.nbytes)])
        whole = {k: sum(int(a.nbytes) for a in jax.tree.leaves(v))
                 for k, v in params.items()}
        print(json.dumps({"line": line, "engine": seen, "shards": shards,
                          "whole": whole}))
    """)
    got = json.loads(out.strip().splitlines()[-1])
    line = got["line"]
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["max_logit_gap"]["limit"] == 0.05
    assert line["device"]["count"] == 4 and line["failed"] == 0
    assert got["engine"] == {"backend": "pipelined", "stages": 4,
                             "blocks": [1, 1, 1, 1], "seal": False}
    whole = got["whole"]
    replicated = sum(v for k, v in whole.items() if k != "blocks")
    assert set(whole) == {"embed", "ln_f", "head", "blocks"}
    assert sorted(got["shards"]) == ["0", "1", "2", "3"]
    for dev, held in got["shards"].items():
        blocks = [(start, n) for top, start, n in held if top == "blocks"]
        # every block leaf holds layer ``dev`` alone on device ``dev``
        assert {tuple(s) for s, _ in blocks} == {(int(dev),)}
        assert sum(n for _, n in blocks) == whole["blocks"] // 4
        assert sum(n for _, _, n in held) == whole["blocks"] // 4 \
            + replicated


def test_sharded_draw_gives_the_same_values():
    """The weights of a seed do not depend on the mesh they are drawn
    over."""
    out = run_on_devices("""
        import jax, numpy as np
        import dense_weights, harness
        from jax.sharding import AxisType
        m = harness.model_dims(harness.load_json("{data}/tiny-p4.json"))
        mesh = jax.make_mesh((4, 1), ("pod", "data"),
                             axis_types=(AxisType.Auto,) * 2)
        for seed in (0, 7, {seed}):
            a = dense_weights.init(m, seed)
            b = dense_weights.init(m, seed, mesh=mesh)
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                assert len(y.sharding.device_set) == 4
                np.testing.assert_array_equal(
                    np.asarray(x.astype(np.float32)),
                    np.asarray(y.astype(np.float32)))
        print("same")
    """.format(data=DATA, seed=SEED))
    assert out.strip().splitlines()[-1] == "same"


@pytest.mark.parametrize("serve, chips, why", [
    ({"backend": "pipelined", "num_stages": 4}, 1, "num_stages 4 differs"),
    ({"backend": "pipelined", "num_stages": 2}, 4, "num_stages 2 differs"),
    ({"backend": "pipelined", "num_stages": 4, "num_slots": 8}, 4,
     "num_slots"),
    ({"backend": "replicas", "num_stages": 4}, 4, "backend 'replicas'"),
    ({"num_stages": 4, "no_such_field": 1}, 4, "no_such_field"),
])
def test_plan_that_does_not_fit_is_refused(tmp_path, monkeypatch, serve,
                                           chips, why):
    """A ``serve`` whose stages differ from the cell's chips, or that sets
    what the mix sizes or what the engine lacks, is refused by the cell's
    name before any weights are drawn."""
    cfg = harness.load_json(os.path.join(DATA, "tiny-p4.json"))
    cfg["serve"] = serve
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(cfg))
    bench = bench_with("tiny-p4", chips)
    bench["configs"][0]["file"] = str(path)

    def drawn(*a, **kw):
        raise AssertionError("weights drawn for a refused cell")

    monkeypatch.setattr(dense_weights, "init", drawn)
    with pytest.raises(ValueError, match="'tiny-p4.chat'") as err:
        harness.find_cell(bench, "tiny-p4.chat", traffic_dir=DATA)
    assert why in str(err.value)


def test_one_chip_cell_is_served_as_before():
    """Without ``serve`` the engine's configuration is field for field the
    one the harness always built: one local stage."""
    from repro.serving import EngineConfig
    cell = harness.find_cell(bench_with("tiny", 1), "tiny.chat",
                             traffic_dir=DATA)
    assert cell.backend == "local"
    assert harness.serving_mesh(cell, jax.devices()[:1]) is None
    m = harness.model_dims(cell.config)
    eng = harness.build_engine(cell, dense_weights.init(m, SEED), m)
    assert eng.backend_kind == "local" and eng.mesh is None
    assert eng.config == EngineConfig(
        num_slots=4, num_stages=1, num_microbatches=1, prompt_capacity=80,
        request_capacity=128, page_size=8, num_pages=41,
        page_policy="demand", preempt_policy="swap", prefix_sharing=True,
        prefill_chunk=32)


def test_unsharded_draw_is_as_before():
    """Without a mesh the draw is the single jitted call it always was, on
    the default device, bit for bit."""
    m = harness.model_dims(harness.load_json(os.path.join(DATA,
                                                          "tiny.json")))
    spec = dense_weights.shapes(m)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=dense_weights._is_leaf)

    def draw(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, fan_in) in zip(keys, leaves):
            if fan_in is None:
                out.append(jnp.ones(shape, jnp.bfloat16))
            else:
                std = 1.0 / math.sqrt(fan_in)
                out.append((jax.random.normal(k, shape, jnp.bfloat16)
                            * jnp.asarray(std, jnp.bfloat16)
                            ).astype(jnp.bfloat16))
        return jax.tree.unflatten(treedef, out)

    want = jax.jit(draw)(dense_weights.base_key(SEED))
    got = dense_weights.init(m, SEED)
    for x, y in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert y.devices() == {jax.devices()[0]}
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x.view(jnp.uint16)),
                                      np.asarray(y.view(jnp.uint16)))


def _run(**kw):
    base = dict(cell="c", seconds=10.0, setup_s=1.0, tracks=[],
                counters={}, flops=4e12, bytes=2e10,
                peaks={"bf16_flops_per_s": 2e12, "hbm_bytes_per_s": 1e10})
    base.update(kw)
    return harness.Run(**base)


@pytest.mark.parametrize("chips", [1, 4])
def test_step_shares_divide_by_the_cells_chips(chips):
    """``step_mfu`` and ``step_hbm_share`` over the window's seconds times
    the cell's chips times one chip's peak: on one chip what they always
    read."""
    run = _run(chips=chips)
    assert harness.reader("step_mfu")(run) == pytest.approx(20.0 / chips)
    assert harness.reader("step_hbm_share")(run) == \
        pytest.approx(20.0 / chips)
    assert _run().chips == 1


def test_host_ms_per_step():
    read = harness.reader("host_ms_per_step")
    run = _run(counters={"span_s.serve.step": 2.0,
                         "span_s.serve.device_wait": 1.5,
                         "span_n.serve.dispatch": 100})
    assert read(run) == pytest.approx(5.0)
    assert read(_run(counters={"decode_steps": 3})) is None
    run.counters["span_n.serve.dispatch"] = 0
    assert read(run) is None


def test_trace_readers():
    """``prefill_ms_per_chunk`` and ``paged_attn_roofline`` read the
    reduced trace, and read nothing where the trace or the engine lacks
    what they read."""
    chunk = harness.reader("prefill_ms_per_chunk")
    roof = harness.reader("paged_attn_roofline")
    trace = {"devices": 2,
             "programs": [["jit_decode_step", 5.0, 150.0],
                          ["jit_prefill_chunk", 0.3, 4.0]],
             "scopes": [["jit_decode_step/kv_write", 0.5],
                        ["jit_decode_step/paged_attention", 0.25]]}
    run = _run(trace=trace, traced_attn_bytes=2e8)
    assert chunk(run) == pytest.approx(75.0)
    # 2e8 bytes over 0.25 s on each of 2 devices at 1e10 bytes/s
    assert roof(run) == pytest.approx(100.0 * 2e8 / (0.5 * 1e10))
    for bare in (_run(), _run(trace={"devices": 1}),
                 _run(trace=dict(trace, programs=[], scopes=[]),
                      traced_attn_bytes=2e8)):
        assert chunk(bare) is None and roof(bare) is None
    # no decode step was counted while the profiler traced
    assert roof(_run(trace=trace)) is None


@pytest.mark.parametrize("name", ["engine", "small"])
def test_reduce_trace_keeps_trace_reduce_and_adds_programs(name):
    """On the recorded v5e traces the harness's reduction reads busy time,
    the window, the top operations and the idle gaps exactly as
    ``trace_reduce`` does, and adds each program's device time and each
    named scope's self time."""
    path = os.path.join(DATA, f"{name}.xplane.pb")
    scopes = {}
    if name == "engine":
        with open(os.path.join(DATA, "engine.scopes.json")) as f:
            scopes = json.load(f)
    got = harness.reduce_trace(path, scopes)
    want = trace_reduce.reduce_file(path)
    for key in want:
        assert got[key] == want[key], key
    progs = {n: (t, k) for n, t, k in got["programs"]}
    assert progs and all(t > 0 and k >= 1 for t, k in progs.values())
    assert "scopes" in got
    if name == "engine":
        assert progs["jit_prefill_chunk"][1] == 3
        scoped = dict(got["scopes"])
        assert scoped["jit_decode_step/paged_attention"] > 0
        run = _run(trace=got, traced_attn_bytes=1e3,
                   peaks={"hbm_bytes_per_s": 819e9})
        assert harness.reader("prefill_ms_per_chunk")(run) > 0
        assert 0 < harness.reader("paged_attn_roofline")(run) < 100
