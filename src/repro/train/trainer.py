"""Training loop with checkpoint/restart, async saving, and metric logging.

The trainer is the *producer* side of Asyncval: it trains, periodically
commits checkpoints to ``ckpt_dir`` (two-phase commit), and never waits for
validation.  The validator (``repro.core.validator``) is the consumer.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint as ckpt
from repro.train.optim import Optimizer


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 10
    ckpt_dir: Optional[str] = None
    keep_last: int = 0              # 0 = keep all (validator may lag)
    log_every: int = 10
    async_save: bool = True
    grad_accum: int = 1
    # convergence control plane: the async validator's EarlyStopController
    # publishes its verdict as an atomic marker file; the trainer polls for
    # it between steps (a single os.path.exists — training halts
    # asynchronously, it NEVER waits on validation).
    stop_file: Optional[str] = None
    stop_poll_every: int = 1        # steps between marker polls
    # lazy snapshot hand-off (repro.handoff.SnapshotChannel): publish a
    # host-resident param snapshot the moment the device->host copy lands,
    # while the durable ckpt.save races in the background — the validator
    # scores it without waiting for serialization or watcher polling.
    # None keeps the classic durable-only hand-off.
    snapshots: Any = None


def make_train_step(loss_fn: Callable, optimizer: Optimizer,
                    grad_accum: int = 1):
    """Build a jit-able (params, opt_state, batch) -> (params, opt_state, metrics).

    ``loss_fn(params, batch) -> (loss, metrics)``.  With grad_accum > 1 the
    batch's leading axis is split into microbatches and gradients averaged
    (lax.scan — compile size independent of accumulation factor).
    """

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(params, opt_state, batch):
        if grad_accum <= 1:
            (loss, aux), grads = grad_fn(params, batch)
        else:
            def micro(carry, mb):
                (l, a), g = grad_fn(params, mb)
                acc_l, acc_g = carry
                return (acc_l + l,
                        jax.tree_util.tree_map(jnp.add, acc_g, g)), a
            microbatches = jax.tree_util.tree_map(
                lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum)
                                    + x.shape[1:]), batch)
            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), auxs = jax.lax.scan(
                micro, (jnp.zeros((), jnp.float32), zero_g), microbatches)
            loss = loss / grad_accum
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, grads)
            aux = jax.tree_util.tree_map(lambda a: a[-1], auxs)
        new_params, new_opt_state = optimizer.update(grads, opt_state, params)
        metrics = {"loss": loss, **aux}
        return new_params, new_opt_state, metrics

    return step


class Trainer:
    """CPU-runnable end-to-end trainer (examples / integration tests).

    Resumable: on construction it restores the latest committed checkpoint
    (params, optimizer state, data cursor, RNG) if one exists — node failure
    recovery is "restart the binary".
    """

    def __init__(self, cfg: TrainerConfig, loss_fn: Callable,
                 optimizer: Optimizer, init_params: Any,
                 batch_iter: Callable[[int], Any],
                 logger: Optional[Any] = None,
                 telemetry=None):
        self.cfg = cfg
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.batch_iter = batch_iter          # step -> batch (deterministic)
        self.logger = logger
        # observation only: a `produced` lifecycle event per saved
        # checkpoint (the first edge of the checkpoint-to-verdict latency)
        self.telemetry = telemetry
        self.saver = ckpt.AsyncSaver()
        self._step_fn = jax.jit(make_train_step(loss_fn, optimizer,
                                                cfg.grad_accum))

        self.step = 0
        self.params = init_params
        self.opt_state = optimizer.init(init_params)
        self.stopped_early = False
        self.stop_verdict: Optional[dict] = None
        self._last_saved_step: Optional[int] = None
        if cfg.ckpt_dir:
            for latest in reversed(ckpt.list_steps(cfg.ckpt_dir)):
                # virtual checkpoints (control-plane ensembles) carry no
                # optimizer state — resume from the newest TRAINED one.
                if ckpt.read_extra(cfg.ckpt_dir, latest).get("virtual"):
                    continue
                state, extra = ckpt.restore(cfg.ckpt_dir, latest)
                self.params = state["params"]
                self.opt_state = state["opt_state"]
                self.step = int(extra.get("step", latest))
                break

    def _publish_snapshot(self, step, host_tree):
        """Async-saver host-copy hook: hand the validator a snapshot before
        the durable save starts (runs on the saver's background thread)."""
        from repro.handoff import ParamSnapshot
        self.cfg.snapshots.publish(ParamSnapshot.from_tree(step, host_tree))

    def _save(self):
        if not self.cfg.ckpt_dir:
            return
        state = {"params": self.params, "opt_state": self.opt_state}
        extra = {"step": self.step, "wall_time": time.time()}
        ch = self.cfg.snapshots
        tel = self.telemetry
        if tel is not None:
            # first edge of the checkpoint-to-verdict latency, whichever
            # hand-off route wins the race
            tel.mark("produced", self.step)
        if self.cfg.async_save:
            self.saver.save(
                self.cfg.ckpt_dir, self.step, state, extra,
                on_host_copy=self._publish_snapshot if ch is not None
                else None,
                on_durable=ch.mark_durable if ch is not None else None,
                on_failure=ch.mark_failed if ch is not None else None)
        else:
            ckpt.save(self.cfg.ckpt_dir, self.step, state, extra)
            if ch is not None:
                # degenerate (already durable) hand-off: publish after the
                # blocking save so sync mode keeps one code path downstream
                self._publish_snapshot(self.step, state)
                ch.mark_durable(self.step)
        self._last_saved_step = self.step
        if tel is not None:
            # async saves commit later; the event marks hand-off to the
            # save path, the COMMIT-marker mtime remains the durable edge
            tel.event("produced", step=self.step,
                      async_save=self.cfg.async_save)

    def _stop_requested(self) -> bool:
        """Poll the control plane's STOP marker (async early stopping)."""
        if not self.cfg.stop_file:
            return False
        if self.step % max(self.cfg.stop_poll_every, 1) != 0:
            return False
        from repro.control.earlystop import stop_requested
        verdict = stop_requested(self.cfg.stop_file)
        if verdict is None:
            return False
        self.stop_verdict = verdict
        return True

    def run(self, on_metrics: Optional[Callable[[int, dict], None]] = None):
        history = []
        prev = None
        while self.step < self.cfg.total_steps:
            if self._stop_requested():
                self.stopped_early = True
                break
            batch = self.batch_iter(self.step)
            self.params, self.opt_state, metrics = self._step_fn(
                self.params, self.opt_state, batch)
            # at most one step queued behind the running one: every queued
            # step holds its own copy of the params and optimizer state
            if prev is not None:
                jax.block_until_ready(prev)
            prev = metrics
            self.step += 1
            # log/notify BEFORE committing the checkpoint: consumers of the
            # metrics feed (the control plane's train-loss lookup) are then
            # guaranteed to know about step t before any validator can see
            # checkpoint t — keeps online decisions == offline replay.
            if self.step % self.cfg.log_every == 0 or \
                    self.step == self.cfg.total_steps:
                m = {k: float(v) for k, v in metrics.items()}
                history.append((self.step, m))
                if self.logger is not None:
                    self.logger.log(self.step, m)
                if on_metrics is not None:
                    on_metrics(self.step, m)
            if self.step % self.cfg.ckpt_every == 0 \
                    or self.step == self.cfg.total_steps:
                self._save()
        if self.stopped_early and self.cfg.ckpt_dir \
                and self._last_saved_step != self.step:
            self._save()    # commit the final state for selection/ensembling
        self.saver.wait()
        return history
