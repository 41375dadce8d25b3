"""Optimizer construction from ``OptimizationConfig``.

Rebuild of the reference's ``configure_optimizers``
(``/root/reference/EventStream/transformer/lightning_modules/generative_modeling.py:460-485``):
AdamW with configurable weight decay, LR warming up linearly from 0 to
``init_lr`` then decaying polynomially to ``end_lr`` — the exact schedule of
HuggingFace's ``get_polynomial_decay_schedule_with_warmup``. Gradient
accumulation (``accumulate_grad_batches`` in Lightning) is ``optax.MultiSteps``.
"""

from __future__ import annotations

import jax.numpy as jnp
import optax

from ..models.config import OptimizationConfig
from ..utils.scopes import host_spanned


def polynomial_decay_with_warmup(
    init_lr: float,
    end_lr: float,
    num_warmup_steps: int,
    num_training_steps: int,
    power: float = 1.0,
) -> optax.Schedule:
    """LR schedule matching HF ``get_polynomial_decay_schedule_with_warmup``.

    step < warmup:  init_lr · step / warmup
    step ≥ total:   end_lr
    otherwise:      end_lr + (init_lr − end_lr) · (1 − (step − warmup)/(total − warmup))^power
    """
    if init_lr <= end_lr:
        raise ValueError(f"end_lr ({end_lr}) must be smaller than init_lr ({init_lr})")

    def schedule(step):
        step = jnp.asarray(step, dtype=jnp.float32)
        warmup = init_lr * step / jnp.maximum(num_warmup_steps, 1)
        remaining = 1.0 - (step - num_warmup_steps) / jnp.maximum(
            num_training_steps - num_warmup_steps, 1
        )
        decay = (init_lr - end_lr) * remaining**power + end_lr
        lr = jnp.where(step < num_warmup_steps, warmup, decay)
        return jnp.where(step >= num_training_steps, end_lr, lr)

    return schedule


@host_spanned("startup/build_step", id="startup")
def build_optimizer(
    optimization_config: OptimizationConfig,
) -> tuple[optax.GradientTransformation, optax.Schedule]:
    """AdamW + warmup/polynomial-decay schedule (+ MultiSteps accumulation).

    Returns ``(tx, schedule)``; the schedule is also returned standalone so
    training loops can log the current LR (the reference's
    ``LearningRateMonitor``).
    """
    oc = optimization_config
    if oc.max_training_steps is None or oc.lr_num_warmup_steps is None:
        raise ValueError(
            "OptimizationConfig.max_training_steps / lr_num_warmup_steps are unset; "
            "call optimization_config.set_to_dataset(train_dataset) first."
        )
    schedule = polynomial_decay_with_warmup(
        init_lr=oc.init_lr,
        end_lr=oc.end_lr,
        num_warmup_steps=oc.lr_num_warmup_steps,
        num_training_steps=oc.max_training_steps,
        power=oc.lr_decay_power,
    )
    tx = optax.adamw(learning_rate=schedule, weight_decay=oc.weight_decay)
    if oc.gradient_accumulation is not None and oc.gradient_accumulation > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=oc.gradient_accumulation)
    return tx, schedule
