"""Fault-tolerant QAT training driver.

Flow (paper §4/§5): finetune fp teacher (or load) -> calibrate (weight scales
abs-max, activation scales percentile) -> QAT with LSQ-MSE scale gradients and
MINI distillation -> deploy int4/int8.

Fault tolerance: atomic checkpoints every --ckpt-every steps and on SIGTERM;
restart auto-resumes from the latest complete step (crash mid-save can never
corrupt it — checkpoint/manager.py). A straggler watchdog flags steps slower
than k x EMA (on real pods this feeds the controller's restart policy).

Runs single-host on any device count (CPU smoke: 1 device); the same step
function jit-compiles under the production mesh in dryrun.py.
"""
from __future__ import annotations

import argparse
import signal
import time

import jax
import jax.numpy as jnp


def build_train_step(plan, hparams, teacher=None, teacher_plan=None):
    """Returns train_step(params, opt, batch) -> (params, opt, metrics).

    ``plan``/``teacher_plan`` are ``repro.deploy.ExecutionPlan``s (student
    QAT plan and fp teacher plan)."""
    from ..core.distill import (combine_losses, hidden_state_loss,
                                minilm_losses, output_loss)
    from ..models import api
    from ..models.transformer import lm_loss
    from ..optim import adam_update, linear_warmup_decay

    cfg = plan.cfg
    teacher_cfg = teacher_plan.cfg if teacher_plan is not None else None
    sched = linear_warmup_decay(hparams.total_steps, hparams.warmup_frac)
    lr_by_group = {"weights": hparams.lr_weights,
                   "act_scale": hparams.lr_act_scale,
                   "weight_scale": hparams.lr_weight_scale}
    distill = teacher is not None

    def loss_fn(params, batch):
        inputs = {k: v for k, v in batch.items() if k != "labels"}
        logits, _, taps_s, aux = api.forward(params, plan,
                                             want_taps=distill, **inputs)
        l_train = lm_loss(logits, batch["labels"]) + aux
        if not distill:
            return l_train, {"loss/train": l_train}
        t_logits, _, taps_t, _ = api.forward(teacher, teacher_plan,
                                             want_taps=True, **inputs)
        l_out = output_loss(logits, jax.lax.stop_gradient(t_logits))
        taps_t = jax.lax.stop_gradient(taps_t)
        if taps_s is not None and "q" in (taps_s or {}):
            R = min(cfg.num_heads, teacher_cfg.num_heads)
            l_attn, l_val = minilm_losses(taps_s, taps_t, R)
        else:  # attention-free family: hidden-state distill (DESIGN.md §5)
            l_attn = hidden_state_loss(taps_s["hidden"], taps_t["hidden"])
            l_val = jnp.zeros(())
        total, parts = combine_losses(l_train, l_out, l_attn, l_val,
                                      hparams.alpha, hparams.beta)
        return total, parts

    def train_step(params, opt, batch):
        (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        params, opt = adam_update(params, grads, opt,
                                  lr_by_group=lr_by_group, schedule_fn=sched,
                                  b1=hparams.adam_b1, b2=hparams.adam_b2,
                                  eps=hparams.adam_eps,
                                  grad_clip=hparams.grad_clip)
        return params, opt, parts

    return train_step


class StragglerWatchdog:
    """Flags steps slower than ``factor`` x EMA of recent step times."""

    def __init__(self, factor: float = 3.0, alpha: float = 0.1):
        self.factor, self.alpha, self.ema = factor, alpha, None
        self.flagged: list[tuple[int, float]] = []

    def observe(self, step: int, dt: float) -> bool:
        slow = self.ema is not None and dt > self.factor * self.ema
        if slow:
            self.flagged.append((step, dt))
        self.ema = dt if self.ema is None else (
            (1 - self.alpha) * self.ema + self.alpha * dt)
        return slow


def run_training(cfg, policy, hparams, data_iter, *, ckpt_dir: str,
                 ckpt_every: int = 50, distill_teacher=None, teacher_cfg=None,
                 log_every: int = 10, max_steps=None, on_step=None):
    """The loop: resume -> step -> checkpoint; SIGTERM-safe."""
    from ..checkpoint import CheckpointManager
    from ..deploy import ExecutionPlan
    from ..models import api
    from ..optim import adam_init

    plan = ExecutionPlan.build(cfg, policy)
    teacher_plan = (ExecutionPlan.build(teacher_cfg, None)
                    if teacher_cfg is not None else None)
    params = api.init_model(cfg, jax.random.PRNGKey(0))
    opt = adam_init(params)
    mgr = CheckpointManager(ckpt_dir)
    state = {"params": params, "opt": opt}
    restored, step0 = mgr.restore(state)
    if restored is not None:
        state = restored
        print(f"[train] resumed from step {step0}", flush=True)
    step0 = step0 or 0

    step_fn = jax.jit(build_train_step(plan, hparams,
                                       teacher=distill_teacher,
                                       teacher_plan=teacher_plan))
    stop = {"now": False}

    def _sigterm(signum, frame):  # checkpoint-and-exit on preemption
        stop["now"] = True
    old = signal.signal(signal.SIGTERM, _sigterm)

    watchdog = StragglerWatchdog()
    total = max_steps or hparams.total_steps
    metrics = {}
    try:
        for step in range(step0, total):
            t0 = time.time()
            batch = {k: jnp.asarray(v) for k, v in next(data_iter).items()}
            params, opt, metrics = step_fn(state["params"], state["opt"],
                                           batch)
            state = {"params": params, "opt": opt}
            dt = time.time() - t0
            if watchdog.observe(step, dt):
                print(f"[train] straggler: step {step} took {dt:.2f}s "
                      f"(ema {watchdog.ema:.2f}s)", flush=True)
            if log_every and step % log_every == 0:
                ms = {k: float(v) for k, v in metrics.items()}
                print(f"[train] step {step} {ms} ({dt:.2f}s)", flush=True)
            if on_step is not None:
                on_step(step, state, metrics)
            if ckpt_every and (step + 1) % ckpt_every == 0 or stop["now"]:
                mgr.save(step + 1, state,
                         {k: float(v) for k, v in metrics.items()})
            if stop["now"]:
                print("[train] SIGTERM: checkpointed, exiting", flush=True)
                break
    finally:
        signal.signal(signal.SIGTERM, old)
    return state, {k: float(v) for k, v in metrics.items()}


def main(argv=None):
    from ..configs import TrainHParams, get_config, reduced
    from ..core.policy import QuantPolicy
    from ..data import lm_batches

    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="stablelm-3b")
    p.add_argument("--reduced", action="store_true",
                   help="smoke-size model (CPU)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--int4-last-k", type=int, default=-1)
    p.add_argument("--grad-mode", default="mse", choices=["mse", "ste"])
    p.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    args = p.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    n_units = cfg.dec_layers if cfg.family == "encdec" else cfg.num_layers
    k4 = args.int4_last_k if args.int4_last_k >= 0 else n_units // 2
    policy = QuantPolicy(num_layers=n_units, mode="fake", last_k_int4=k4,
                         grad_mode=args.grad_mode)
    hp = TrainHParams(total_steps=args.steps)
    data = lm_batches(cfg.vocab_size, args.seq, args.batch)
    state, metrics = run_training(cfg, policy, hp, iter(data),
                                  ckpt_dir=args.ckpt_dir,
                                  max_steps=args.steps)
    print("[train] done", metrics)


if __name__ == "__main__":
    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
