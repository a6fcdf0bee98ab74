"""Trainer (checkpoint/restart, async save, grad-accum) + optimizers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import checkpoint as ckpt
from repro.train import optim
from repro.train.trainer import Trainer, TrainerConfig, make_train_step

# ---------------------------------------------------------------------------
# A 2-parameter quadratic problem with deterministic batches.
# ---------------------------------------------------------------------------

TARGET = jnp.asarray([3.0, -2.0])


def loss_fn(params, batch):
    pred = batch["x"] @ params["w"]
    loss = jnp.mean(jnp.square(pred - batch["y"]))
    return loss, {"mse": loss}


def batch_for(step: int, n=16):
    rng = np.random.default_rng(step)
    x = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    return {"x": x, "y": x @ TARGET}


def init_params(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(2,)), jnp.float32)}


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make_opt", [
    lambda: optim.adamw(5e-2, weight_decay=0.0),
    lambda: optim.adafactor(5e-1),
    lambda: optim.compressed(optim.adamw(5e-2, weight_decay=0.0)),
])
def test_optimizers_converge(make_opt):
    opt = make_opt()
    params = init_params()
    state = opt.init(params)
    step = jax.jit(make_train_step(loss_fn, opt))
    for i in range(300):
        params, state, m = step(params, state, batch_for(i))
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(TARGET),
                               atol=0.15)


def test_adamw_matches_reference_update():
    """One AdamW step against a hand-rolled numpy reference."""
    opt = optim.adamw(0.1, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0,
                      max_grad_norm=None)
    params = {"w": jnp.asarray([1.0, 2.0])}
    state = opt.init(params)
    grads = {"w": jnp.asarray([0.5, -1.0])}
    new_p, _ = opt.update(grads, state, params)
    g = np.asarray([0.5, -1.0])
    m = 0.1 * g / (1 - 0.9)
    v = 0.001 * g ** 2 / (1 - 0.999)
    exp = np.asarray([1.0, 2.0]) - 0.1 * m / (np.sqrt(v) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p["w"]), exp, rtol=1e-5)


def test_grad_clipping():
    g = {"a": jnp.asarray([300.0, 400.0])}        # norm 500
    clipped, norm = optim.clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(500.0)
    np.testing.assert_allclose(np.asarray(clipped["a"]),
                               [0.6, 0.8], rtol=1e-5)


def test_int8_compression_error_feedback():
    """Quantize->dequantize error carried forward, not lost."""
    x = jnp.asarray(np.random.default_rng(0).normal(size=(100,)), jnp.float32)
    q, scale = optim.quantize_int8(x)
    assert q.dtype == jnp.int8
    deq = optim.dequantize_int8(q, scale)
    err = np.abs(np.asarray(deq - x))
    assert err.max() <= float(scale) * 0.5 + 1e-6   # round-to-nearest bound


def test_warmup_cosine_schedule():
    sched = optim.warmup_cosine(1.0, 10, 100, final_frac=0.1)
    assert float(sched(0)) == pytest.approx(0.0)
    assert float(sched(10)) == pytest.approx(1.0, abs=0.01)
    assert float(sched(100)) == pytest.approx(0.1, abs=0.01)
    assert float(sched(55)) < 1.0


# ---------------------------------------------------------------------------
# Trainer
# ---------------------------------------------------------------------------

def test_trainer_runs_and_checkpoints(tmp_path):
    cfg = TrainerConfig(total_steps=25, ckpt_every=10,
                        ckpt_dir=str(tmp_path / "ck"), log_every=5)
    tr = Trainer(cfg, loss_fn, optim.adamw(5e-2), init_params(), batch_for)
    hist = tr.run()
    assert tr.step == 25
    assert ckpt.list_steps(cfg.ckpt_dir) == [10, 20, 25]
    assert hist[-1][1]["loss"] < hist[0][1]["loss"]


def test_trainer_keeps_one_step_queued():
    """Each queued step holds its own params and optimizer state, so the
    trainer lets at most one step wait behind the running one: when step
    n is dispatched, step n-2 has finished."""
    def slow_loss(params, batch):
        # device work per step far longer than a dispatch
        m = jnp.eye(256) * 0.5
        m = jax.lax.fori_loop(0, 40, lambda _, a: a @ m + m, m)
        loss, aux = loss_fn(params, batch)
        return loss + 0.0 * m.sum(), aux

    cfg = TrainerConfig(total_steps=12, log_every=100)
    tr = Trainer(cfg, slow_loss, optim.adamw(5e-2), init_params(), batch_for)
    outs, ready = [], []
    step_fn = tr._step_fn

    def spy(*args):
        if len(outs) >= 2:
            ready.append(all(x.is_ready()
                             for x in jax.tree_util.tree_leaves(outs[-2])))
        outs.append(step_fn(*args))
        return outs[-1]

    tr._step_fn = spy
    tr.run()
    assert len(ready) == 10 and all(ready)


def test_trainer_restart_resumes_exactly(tmp_path):
    """Kill-and-restart must produce bit-identical params to an
    uninterrupted run (params + opt state + data cursor restored)."""
    ckdir = str(tmp_path / "ck")
    cfg = TrainerConfig(total_steps=30, ckpt_every=10, ckpt_dir=ckdir,
                        async_save=False)
    # uninterrupted reference
    ref = Trainer(TrainerConfig(total_steps=30, ckpt_every=10,
                                ckpt_dir=str(tmp_path / "ref"),
                                async_save=False),
                  loss_fn, optim.adamw(5e-2), init_params(), batch_for)
    ref.run()
    # interrupted: run to 30 but simulate crash by constructing a trainer
    # that stops at 20 (fresh process restores from step-20 checkpoint)
    t1 = Trainer(TrainerConfig(total_steps=20, ckpt_every=10, ckpt_dir=ckdir,
                               async_save=False),
                 loss_fn, optim.adamw(5e-2), init_params(), batch_for)
    t1.run()
    t2 = Trainer(cfg, loss_fn, optim.adamw(5e-2), init_params(seed=999),
                 batch_for)                      # wrong init: must be ignored
    assert t2.step == 20                          # resumed, not restarted
    t2.run()
    np.testing.assert_array_equal(np.asarray(t2.params["w"]),
                                  np.asarray(ref.params["w"]))


def test_grad_accum_matches_large_batch():
    """grad_accum=4 over a 64-batch == single 64-batch step (linear model)."""
    opt = optim.adamw(1e-2, max_grad_norm=None)
    params = init_params()
    batch = batch_for(0, n=64)
    s1 = jax.jit(make_train_step(loss_fn, opt))
    s4 = jax.jit(make_train_step(loss_fn, opt, grad_accum=4))
    p1, _, m1 = s1(params, opt.init(params), batch)
    p4, _, m4 = s4(params, opt.init(params), batch)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p4["w"]),
                               rtol=1e-5)
    assert float(m1["loss"]) == pytest.approx(float(m4["loss"]), rel=1e-5)


@pytest.mark.parametrize("fail", [False, True],
                         ids=["clean", "failing_validation"])
def test_train_main_exit_code(tmp_path, monkeypatch, fail):
    """``python -m repro.launch.train`` exits 1 when any checkpoint failed
    to validate, 0 when every one validated."""
    from repro.core.suite import ValidationSuite
    from repro.launch import train
    if fail:
        def broken(self, *args, **kwargs):
            raise RuntimeError("injected validation failure")
        monkeypatch.setattr(ValidationSuite, "run_unit", broken)
    rc = train.main(["--workdir", str(tmp_path), "--steps", "2",
                     "--ckpt-every", "2", "--corpus-size", "40",
                     "--n-queries", "6", "--batch-size", "4"])
    assert rc == (1 if fail else 0)
