"""End-to-end training: loss decreases; checkpoint-resume is exact;
preemption saves state."""
import os

import jax
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_arch, reduced, ShapeConfig
from repro.data.tokens import SyntheticTokenStream
from repro.launch.mesh import make_mesh
from repro.models.api import build_model
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.runtime import steps as S
from repro.runtime.train_loop import TrainLoop, TrainLoopConfig


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_arch("llama3.2-1b"))
    api = build_model(cfg, max_seq=32)
    shape = ShapeConfig("t", 32, 4, "train")
    mesh = make_mesh((1, 1), ("data", "model"))
    opt_cfg = AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=60,
                          weight_decay=0.01)
    with jax.set_mesh(mesh):
        step = S.make_train_step(api, mesh, opt_cfg, shape)
    return api, cfg, shape, mesh, step


def _fresh(api, cfg):
    params = api.init(jax.random.PRNGKey(0))
    return params, adamw.init(params)


def test_loss_decreases(setup):
    api, cfg, shape, mesh, step = setup
    params, opt = _fresh(api, cfg)
    data = SyntheticTokenStream(cfg.vocab_size, 4, 32, seed=0, structure=1.0)
    with jax.set_mesh(mesh):
        loop = TrainLoop(train_step=step, params=params, opt_state=opt,
                         data=data, cfg=TrainLoopConfig(total_steps=40))
        out = loop.run()
    losses = out["losses"]
    assert losses[-1] < losses[0] - 1.0, (losses[0], losses[-1])


def test_checkpoint_resume_exact(setup, tmp_path):
    api, cfg, shape, mesh, step = setup
    data = SyntheticTokenStream(cfg.vocab_size, 4, 32, seed=1)

    with jax.set_mesh(mesh):
        # run A: 10 straight steps
        params, opt = _fresh(api, cfg)
        loopA = TrainLoop(train_step=step, params=params, opt_state=opt,
                          data=SyntheticTokenStream(cfg.vocab_size, 4, 32, seed=1),
                          cfg=TrainLoopConfig(total_steps=10))
        outA = loopA.run()

        # run B: 5 steps -> checkpoint -> new loop resumes -> 5 more
        ck = CheckpointManager(str(tmp_path), async_save=False)
        params, opt = _fresh(api, cfg)
        loopB1 = TrainLoop(train_step=step, params=params, opt_state=opt,
                           data=SyntheticTokenStream(cfg.vocab_size, 4, 32, seed=1),
                           ckpt=ck, cfg=TrainLoopConfig(total_steps=5,
                                                        ckpt_every=5))
        loopB1.run()
        params2, opt2 = _fresh(api, cfg)   # junk state, must be overwritten
        loopB2 = TrainLoop(train_step=step, params=params2, opt_state=opt2,
                           data=SyntheticTokenStream(cfg.vocab_size, 4, 32, seed=1),
                           ckpt=ck, cfg=TrainLoopConfig(total_steps=5))
        assert loopB2.try_restore()
        assert loopB2.step == 5
        outB = loopB2.run(5)

    for a, b in zip(jax.tree.leaves(loopA.params), jax.tree.leaves(loopB2.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    np.testing.assert_allclose(outA["losses"][5:], outB["losses"], rtol=1e-6)


def test_preemption_saves(setup, tmp_path):
    api, cfg, shape, mesh, step = setup
    ck = CheckpointManager(str(tmp_path), async_save=False)
    params, opt = _fresh(api, cfg)
    with jax.set_mesh(mesh):
        loop = TrainLoop(train_step=step, params=params, opt_state=opt,
                         data=SyntheticTokenStream(cfg.vocab_size, 4, 32),
                         ckpt=ck, cfg=TrainLoopConfig(total_steps=100))
        loop.preempt()
        out = loop.run()
    assert out["preempted"]
    assert ck.latest_step() is not None


def test_straggler_hook(setup):
    api, cfg, shape, mesh, step = setup
    params, opt = _fresh(api, cfg)
    events = []
    import time as _time

    class SlowData(SyntheticTokenStream):
        def __next__(self):
            if self.step == 6:
                _time.sleep(3.0)   # inject a straggler step
            return super().__next__()

    with jax.set_mesh(mesh):
        loop = TrainLoop(train_step=step, params=params, opt_state=opt,
                         data=SlowData(cfg.vocab_size, 4, 32),
                         cfg=TrainLoopConfig(total_steps=9,
                                             straggler_factor=3.0),
                         on_straggler=lambda s, dt, ema: events.append(s))
        loop.run()
    assert events, "straggler not detected"
