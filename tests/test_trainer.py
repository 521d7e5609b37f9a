"""Fault-tolerance tests: checkpoint/restart, preemption, stragglers,
grad compression, elastic restore."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointConfig, CheckpointManager
from repro.configs import get_arch
from repro.data.pipeline import LMDataConfig, lm_batch
from repro.models import Model
from repro.models.base import init_params
from repro.optim import AdamWConfig, GradCompressionConfig
from repro.train.trainer import Trainer, TrainerConfig


def _mk_trainer(tmp_path, steps=8, every=4, compression=False, name="ck"):
    cfg = get_arch("smollm_135m", smoke=True)
    model = Model(cfg)
    data = LMDataConfig(vocab=cfg.vocab, seq_len=16, global_batch=2)
    tc = TrainerConfig(
        total_steps=steps,
        log_every=1,
        opt=AdamWConfig(lr=1e-3),
        compression=GradCompressionConfig(enabled=compression, min_numel=64),
        checkpoint=CheckpointConfig(directory=str(tmp_path / name),
                                    every_steps=every, async_save=False),
    )
    return Trainer(model, tc, lambda s: lm_batch(data, s))


def _tree_equal(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    if len(fa) != len(fb):
        return False
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(fa, fb, strict=True))


def test_loss_decreases():
    cfg = get_arch("smollm_135m", smoke=True)
    model = Model(cfg)
    data = LMDataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4)
    tc = TrainerConfig(total_steps=40, log_every=1, opt=AdamWConfig(lr=3e-3))
    tr = Trainer(model, tc, lambda s: lm_batch(data, s))
    tr.run()
    losses = [m["loss"] for m in tr.metrics_log]
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_checkpoint_resume_bit_exact(tmp_path):
    """Train 8 straight vs train 4 + preempt + resume 4: identical final
    state.  (Both legs use total_steps=8 so the LR schedule is identical.)"""
    t_full = _mk_trainer(tmp_path, steps=8, every=100, name="full")
    s_full, _ = t_full.run()

    t_a = _mk_trainer(tmp_path, steps=8, every=4, name="resume")

    def preempt(step, state, metrics):
        if step == 3:
            t_a.request_preemption()

    t_a.run(step_hook=preempt)  # stops + checkpoints at step 4
    t_b = _mk_trainer(tmp_path, steps=8, every=4, name="resume")
    state_b, last = t_b.run()  # resumes from 4, runs to 8
    assert last == 8
    assert _tree_equal(s_full.params, state_b.params)
    assert _tree_equal(s_full.opt.m, state_b.opt.m)


def test_preemption_checkpoints_and_resumes(tmp_path):
    tr = _mk_trainer(tmp_path, steps=100, every=1000, name="pre")
    hook_calls = []

    def hook(step, state, metrics):
        hook_calls.append(step)
        if step == 3:
            tr.request_preemption()

    state, last = tr.run(step_hook=hook)
    assert last == 4  # stopped right after step 3
    mgr = CheckpointManager(tr.cfg.checkpoint)
    assert mgr.latest_step() == 4
    # resume picks up where preemption left off
    tr2 = _mk_trainer(tmp_path, steps=6, every=1000, name="pre")
    state2, start = tr2.init_state()
    assert start == 4


def test_straggler_watchdog(tmp_path):
    tr = _mk_trainer(tmp_path, steps=20, every=1000, name="strag")

    def hook(step, state, metrics):
        if step == 15:
            time.sleep(1.0)  # inject a straggler step

    tr.run(step_hook=hook)
    assert any(e["step"] == 15 for e in tr.straggler_events)


def test_grad_compression_converges(tmp_path):
    """QSQ-compressed grads with error feedback still reduce the loss."""
    cfg = get_arch("smollm_135m", smoke=True)
    model = Model(cfg)
    data = LMDataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4)
    tc = TrainerConfig(
        total_steps=40, log_every=1, opt=AdamWConfig(lr=3e-3),
        compression=GradCompressionConfig(enabled=True, min_numel=64),
    )
    tr = Trainer(model, tc, lambda s: lm_batch(data, s))
    tr.run()
    losses = [m["loss"] for m in tr.metrics_log]
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_grad_compression_wire_bytes_reported():
    from repro.train.state import train_state_descs
    from repro.train.step import make_train_step

    cfg = get_arch("smollm_135m", smoke=True)
    model = Model(cfg)
    cc = GradCompressionConfig(enabled=True, min_numel=64)
    step = make_train_step(model, AdamWConfig(), cc)
    state = init_params(jax.random.PRNGKey(0), train_state_descs(model, cc))
    tok = jnp.zeros((2, 16), jnp.int32)
    _, metrics = step(state, {"tokens": tok, "labels": tok})
    assert float(metrics["grad_wire_bytes"]) > 0


def test_elastic_restore_different_sharding(tmp_path):
    """Checkpoint saved unsharded restores under an explicit NamedSharding
    (mesh-shape change path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint.manager import load_pytree, save_pytree

    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    path = tmp_path / "elastic.npz"
    save_pytree(tree, path)

    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    shardings = {"w": NamedSharding(mesh, P("data", None))}
    restored = load_pytree(tree, path, sharding=shardings)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
    assert restored["w"].sharding == shardings["w"]


def test_checkpoint_gc_keeps_last(tmp_path):
    mgr = CheckpointManager(CheckpointConfig(directory=str(tmp_path / "gc"),
                                             keep_last=2, async_save=False))
    tree = {"x": jnp.zeros((4,))}
    for s in (1, 2, 3, 4):
        mgr.save(tree, s, wait=True)
    assert mgr.all_steps() == [3, 4]


def test_wire_export(tmp_path):
    from repro.core.policy import QuantPolicy
    from repro.core.qsq import QSQConfig

    mgr = CheckpointManager(CheckpointConfig(directory=str(tmp_path / "wire"),
                                             async_save=False))
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.1}
    p = mgr.export_wire(params, QuantPolicy(base=QSQConfig(group_size=16),
                                            min_numel=256))
    assert p.exists()
    # wire artifact must be much smaller than f32
    assert p.stat().st_size < 64 * 32 * 4 * 0.5
