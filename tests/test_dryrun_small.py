"""Dry-run machinery on a small 8-device mesh (subprocess)."""
import pytest


def test_lower_compile_small_mesh(subproc):
    code = """
import jax, jax.numpy as jnp
from repro.configs import get_arch, reduced, ShapeConfig
from repro.launch.mesh import make_mesh
from repro.models.api import build_model
from repro.optim.adamw import AdamWConfig
from repro.runtime import steps as S
from repro.utils import hlo_analysis as H

mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
cfg = reduced(get_arch('llama3.2-1b'))
api = build_model(cfg, max_seq=64)
shape = ShapeConfig('t', 64, 4, 'train')
ab = S.abstract_inputs(api, shape)
with jax.set_mesh(mesh):
    step = S.make_train_step(api, mesh, AdamWConfig(), shape)
    lowered = step.lower(ab['params'], ab['opt'], ab['batch'],
                         jax.ShapeDtypeStruct((), jnp.int32))
    compiled = lowered.compile()
mem = compiled.memory_analysis()
assert mem.argument_size_in_bytes > 0
coll = H.walk_collectives(compiled.as_text())
total = sum(coll.values())
assert total > 0, 'expected collectives on a sharded mesh'
print('COLL', coll)
print('OK')
"""
    out = subproc(code, devices=8, timeout=1200)
    assert "OK" in out


def test_decode_cell_small_mesh(subproc):
    code = """
import jax, jax.numpy as jnp
from repro.configs import get_arch, reduced, ShapeConfig
from repro.launch.mesh import make_mesh
from repro.models.api import build_model
from repro.runtime import steps as S

mesh = make_mesh((2, 4), ('data', 'model'))
cfg = reduced(get_arch('glm4-9b'))
api = build_model(cfg, max_seq=64)
shape = ShapeConfig('d', 64, 4, 'decode')
ab = S.abstract_inputs(api, shape)
with jax.set_mesh(mesh):
    step = S.make_decode_step(api, mesh, shape)
    compiled = step.lower(ab['params'], ab['cache'], ab['batch']).compile()
print('OK')
"""
    out = subproc(code, devices=8, timeout=1200)
    assert "OK" in out
