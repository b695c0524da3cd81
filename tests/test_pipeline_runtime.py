"""Pipelined serve (shard_map over pod) vs sequential decode — multi-device,
run in subprocesses so the main process keeps 1 device."""
import pytest


PIPE_CODE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
import repro.models.layers as L
L.DEFAULT_DTYPE = jnp.float32         # f32 -> bit-exact comparison
from repro.configs import get_arch, reduced
from repro.models.api import build_model
from repro.runtime.pipeline import PipelinedDecoder

cfg = reduced(get_arch('{arch}'))
api = build_model(cfg, max_seq=32)
params = api.init(jax.random.PRNGKey(0))
params = jax.tree.map(lambda x: x.astype(jnp.float32)
                      if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
B, S = 8, 16
tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size, jnp.int32)
_, cache = jax.jit(api.prefill_fn)(params, {{'tokens': tokens}})
seg = api.model.segments[0].name
cache[seg] = jax.tree.map(
    lambda a: jnp.pad(a, [(0,0)]*3+[(0,16)]+[(0,0)]) if a.ndim == 5 else a,
    cache[seg])
new_tok = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0, cfg.vocab_size, jnp.int32)
ref_logits, ref_cache = jax.jit(api.decode_fn)(params, cache, {{'tokens': new_tok}})

mesh = jax.make_mesh((2, 2), ('pod', 'data'), axis_types=(AxisType.Auto,)*2)
with jax.set_mesh(mesh):
    dec = PipelinedDecoder(api, mesh, num_stages=2, num_microbatches=4,
                           seal_boundary={seal}, stage_blocks={blocks})
    lg, nc = jax.jit(dec.build())(params, cache, {{'tokens': new_tok}}, jnp.uint32(7))
err = np.abs(np.asarray(lg) - np.asarray(ref_logits)).max()
rel = err / (np.abs(np.asarray(ref_logits)).max() + 1e-9)
assert int(nc['len']) == int(ref_cache['len'])
# uneven boundaries: padded slots must not corrupt the unstaged cache
for a, b in zip(jax.tree.leaves(nc[seg]), jax.tree.leaves(ref_cache[seg])):
    ca, cb = np.asarray(a, np.float64), np.asarray(b, np.float64)
    cerr = np.abs(ca - cb).max() / (np.abs(cb).max() + 1e-9)
    assert cerr < {tol}, cerr
print('REL_ERR', rel)
assert rel < {tol}, rel
print('OK')
"""


@pytest.mark.parametrize("arch", ["llama3.2-1b", "hymba-1.5b"])
def test_pipelined_decode_exact_f32(subproc, arch):
    out = subproc(PIPE_CODE.format(arch=arch, seal="False", tol=1e-5,
                                   blocks="None"),
                  devices=4)
    assert "OK" in out


@pytest.mark.parametrize("blocks", ["[3, 1]", "[1, 3]"])
def test_pipelined_decode_uneven_stages_exact_f32(subproc, blocks):
    """Solver-produced uneven boundaries (reduced cfg: 4 blocks as 3/1 or
    1/3) must reproduce the unpipelined decode logits exactly."""
    out = subproc(PIPE_CODE.format(arch="llama3.2-1b", seal="False", tol=1e-5,
                                   blocks=blocks),
                  devices=4)
    assert "OK" in out


NON_PREFIX_CODE = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
import repro.models.layers as L
L.DEFAULT_DTYPE = jnp.float32
from repro.configs import get_arch, reduced
from repro.core import cost_model as CM
from repro.core.planner import (LayerProfile, PlacementSpec, ResourceGraph,
                                solve)
from repro.models.api import build_model
from repro.runtime.pipeline import PipelinedDecoder

# similarity bump at layer 3's input: that layer must return to a TEE, so
# the optimum sandwiches a fast untrusted device between two slow enclaves
sims = [0.3, 0.3, 0.9, 0.1]
profs = [LayerProfile(f'b{i}', 2e8, 2e5, sims[i], params_bytes=1e6)
         for i in range(4)]
g = ResourceGraph({'tee0': CM.TEE,
                   'tee1': dataclasses.replace(CM.TEE, name='tee1'),
                   'gpu0': CM.GPU}, {}, CM.WAN_30MBPS)
px = solve(profs, g, n=10_800, delta=0.5, solver='exhaustive')
sg = solve(profs, g, n=10_800, delta=0.5, solver='segment-dp')
assert sg.best.t_chunk < px.best.t_chunk * (1 - 1e-6), \\
    (sg.best.t_chunk, px.best.t_chunk)
spec = PlacementSpec.from_placement(sg.best.placement, g)
assert not spec.is_prefix(g), spec.describe()
assert spec.num_segments == 3, spec.describe()
print('plan:', spec.describe(), 'blocks:', spec.stage_sizes())

cfg = reduced(get_arch('llama3.2-1b'))
api = build_model(cfg, max_seq=32)
params = api.init(jax.random.PRNGKey(0))
params = jax.tree.map(lambda x: x.astype(jnp.float32)
                      if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
B = 6
tokens = jax.random.randint(jax.random.PRNGKey(1), (B, 16), 0,
                            cfg.vocab_size, jnp.int32)
_, cache = jax.jit(api.prefill_fn)(params, {'tokens': tokens})
seg = api.model.segments[0].name
cache[seg] = jax.tree.map(
    lambda a: jnp.pad(a, [(0,0)]*3+[(0,16)]+[(0,0)]) if a.ndim == 5 else a,
    cache[seg])
new_tok = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0,
                             cfg.vocab_size, jnp.int32)
ref_logits, _ = jax.jit(api.decode_fn)(params, cache, {'tokens': new_tok})

mesh = jax.make_mesh((3,), ('pod',), axis_types=(AxisType.Auto,))
with jax.set_mesh(mesh):
    dec = PipelinedDecoder.from_spec(api, mesh, spec, num_microbatches=3,
                                     seal_boundary=False)
    assert dec.stage_counts == spec.stage_sizes()
    assert dec.stage_devices == spec.devices()
    lg, _ = jax.jit(dec.build())(params, cache, {'tokens': new_tok},
                                 jnp.uint32(7))
rel = np.abs(np.asarray(lg) - np.asarray(ref_logits)).max() / \\
    (np.abs(np.asarray(ref_logits)).max() + 1e-9)
assert rel < 1e-5, rel
# token-exact: the decoded tokens equal the single-device reference
assert (jnp.argmax(lg, -1) == jnp.argmax(ref_logits, -1)).all()
print('OK')
"""


def test_pipelined_decode_executes_non_prefix_plan_token_exact(subproc):
    """Acceptance: the segment solver finds a strictly-better-than-prefix
    plan (slow enclave sandwich) and PipelinedDecoder.from_spec executes it
    with decode output equal to the single-device reference."""
    out = subproc(NON_PREFIX_CODE, devices=3)
    assert "OK" in out


def test_pipelined_decode_with_sealing(subproc):
    """Sealed boundaries add int8 quantization noise — bounded, not exact."""
    out = subproc(PIPE_CODE.format(arch="llama3.2-1b", seal="True", tol=0.05,
                                   blocks="None"),
                  devices=4)
    assert "OK" in out


def test_compressed_grad_training_converges(subproc):
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs import get_arch, reduced, ShapeConfig
from repro.data.tokens import SyntheticTokenStream
from repro.models.api import build_model
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.optim.compression import init_error_feedback
from repro.runtime import steps as S

cfg = reduced(get_arch('llama3.2-1b'))
api = build_model(cfg, max_seq=32)
shape = ShapeConfig('t', 32, 4, 'train')
mesh = jax.make_mesh((2, 2), ('pod', 'data'), axis_types=(AxisType.Auto,)*2)
opt_cfg = AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=40)
params = api.init(jax.random.PRNGKey(0))
opt = adamw.init(params)
ef = init_error_feedback(params)
data = SyntheticTokenStream(cfg.vocab_size, 4, 32, seed=0, structure=1.0)
with jax.set_mesh(mesh):
    step = S.make_train_step(api, mesh, opt_cfg, shape, compress_pod_grads=True)
    losses = []
    for i in range(30):
        loss, params, opt, ef, gn = step(params, opt, ef, next(data), np.int32(i))
        losses.append(float(loss))
print('FIRST', losses[0], 'LAST', losses[-1])
assert losses[-1] < losses[0] - 1.0
print('OK')
"""
    out = subproc(code, devices=4, timeout=1200)
    assert "OK" in out
