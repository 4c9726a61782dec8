"""The training loop: jitted train_step + checkpoint/restart + straggler
timing + optional gradient compression — the fault-tolerant driver that
launch/train.py runs (and tests exercise with injected failures)."""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.distributed.compression import make_error_feedback_compressor
from repro.runtime.ft import FailureInjector, RestartSupervisor, StragglerDetector

from .optimizer import AdamWConfig, init_state
from .train_state import build_train_step

__all__ = ["TrainLoopConfig", "train"]


@dataclasses.dataclass(frozen=True)
class TrainLoopConfig:
    total_steps: int
    checkpoint_every: int = 50
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep: int = 2
    grad_compression: bool = False
    log_every: int = 10
    host: str = "host0"


def train(
    model,
    dataset,
    opt_cfg: AdamWConfig,
    loop_cfg: TrainLoopConfig,
    *,
    injector: FailureInjector | None = None,
    params=None,
    log: Callable[[str], None] = print,
) -> dict:
    """Runs to ``total_steps`` with restart-on-failure. Returns summary."""
    ckpt = Checkpointer(loop_cfg.checkpoint_dir, keep=loop_cfg.keep)
    if params is None:
        params = model.init(jax.random.key(0))
    opt_state = init_state(opt_cfg, params)
    err_state = None
    compress = None
    if loop_cfg.grad_compression:
        init_err, compress = make_error_feedback_compressor(params)
        err_state = init_err()

    if compress is not None:
        from .optimizer import apply_updates

        def step_fn(params, opt_state, err, batch):
            (_, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
                params, batch
            )
            grads, err = compress(grads, err)
            params, opt_state, opt_m = apply_updates(opt_cfg, params, grads, opt_state)
            metrics = dict(metrics)
            metrics.update(opt_m)
            return params, opt_state, err, metrics

        jitted = jax.jit(step_fn, donate_argnums=(0, 1, 2))
    else:
        jitted = jax.jit(build_train_step(model, opt_cfg), donate_argnums=(0, 1))
    detector = StragglerDetector()
    state = {"params": params, "opt": opt_state, "err": err_state}
    metrics_hist: list[dict] = []

    def save(step):
        tree = {"params": state["params"], "opt": state["opt"]}
        if state["err"] is not None:
            tree["err"] = state["err"]
        ckpt.save(step, tree, metadata={"host": loop_cfg.host})

    def restore() -> int:
        # drain the async writer first: a queued re-save of the step about
        # to be read deletes its directory before publishing it again
        ckpt.wait()
        step = ckpt.latest_step()
        if step is None:
            return 0
        tree = {"params": state["params"], "opt": state["opt"]}
        if state["err"] is not None:
            tree["err"] = state["err"]
        restored = ckpt.restore(jax.tree.map(lambda x: x, tree), step)
        state["params"] = restored["params"]
        state["opt"] = restored["opt"]
        if "err" in restored:
            state["err"] = restored["err"]
        log(f"[ft] restored step {step}")
        return step

    def body(start_step: int) -> int:
        for step, batch in dataset.batches(loop_cfg.total_steps, start_step):
            if injector is not None:
                injector.maybe_fail(step)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            with detector.timing(loop_cfg.host):
                if compress is not None:
                    state["params"], state["opt"], state["err"], m = jitted(
                        state["params"], state["opt"], state["err"], batch
                    )
                else:
                    state["params"], state["opt"], m = jitted(
                        state["params"], state["opt"], batch
                    )
            if step % loop_cfg.log_every == 0:
                mm = {k: float(v) for k, v in m.items()}
                metrics_hist.append({"step": step, **mm})
                log(f"step {step}: {mm}")
            if step and step % loop_cfg.checkpoint_every == 0:
                save(step)
        save(loop_cfg.total_steps - 1)
        ckpt.wait()
        return loop_cfg.total_steps - 1

    sup = RestartSupervisor(restore=restore, max_restarts=5)
    result = sup.run(body, 0)
    result["metrics"] = metrics_hist
    result["stragglers"] = detector.stragglers()
    return result
