"""Optimizer & LR-schedule factory — the config surface training loops use.

The reference leaves optimizers to user TF code (Keras compile); here the
framework provides the standard TPU-training recipes behind one call so
examples, the pipeline Estimator, and user map_funs share them:

    opt, schedule = optim.make_optimizer(
        "adamw", learning_rate=3e-4, warmup_steps=1000,
        total_steps=100_000, schedule="cosine", weight_decay=0.1,
        clip_norm=1.0)

All knobs are plain config values (strings/numbers), so they pass through
`pipeline.Namespace`/argparse unchanged.
"""
import logging

logger = logging.getLogger(__name__)

SCHEDULES = ("constant", "cosine", "linear", "rsqrt")
OPTIMIZERS = ("adam", "adamw", "adamw_fused", "adamw8bit", "sgd", "lion",
              "lion_fused", "adafactor")
# single-pass Pallas kernels (ops/fused_optim): clipping/decay/lr fold INTO
# the fused update instead of an optax.chain around it
_FUSED = ("adamw_fused", "lion_fused")


def make_schedule(learning_rate, schedule="constant", warmup_steps=0,
                  total_steps=None, end_value=0.0):
    """An optax schedule: linear warmup into constant/cosine/linear/rsqrt
    decay.  `total_steps` is required for cosine/linear."""
    import optax

    if schedule not in SCHEDULES:
        raise ValueError(f"schedule={schedule!r} not in {SCHEDULES}")
    if schedule in ("cosine", "linear") and not total_steps:
        raise ValueError(f"schedule={schedule!r} requires total_steps")
    decay_steps = max((total_steps or 0) - warmup_steps, 1)
    if schedule == "constant":
        main = optax.constant_schedule(learning_rate)
    elif schedule == "cosine":
        main = optax.cosine_decay_schedule(learning_rate, decay_steps,
                                           alpha=end_value / learning_rate
                                           if learning_rate else 0.0)
    elif schedule == "linear":
        main = optax.linear_schedule(learning_rate, end_value, decay_steps)
    else:  # rsqrt (the classic transformer schedule tail)
        shift = max(warmup_steps, 1)

        def main(step):
            return learning_rate * (shift ** 0.5) / ((step + shift) ** 0.5)
    if warmup_steps:
        warm = optax.linear_schedule(0.0, learning_rate, warmup_steps)
        return optax.join_schedules([warm, main], [warmup_steps])
    return main


def make_optimizer(name="adamw", learning_rate=1e-3, schedule="constant",
                   warmup_steps=0, total_steps=None, end_value=0.0,
                   weight_decay=0.0, clip_norm=None, b1=None, b2=None,
                   momentum=0.9, decay_mask=None, mu_dtype=None,
                   layouts=None):
    """Build `(optax_optimizer, schedule_fn)` from plain config values.

    `decay_mask` (a pytree-of-bools fn or tree) routes weight decay away
    from biases/norms the usual way, e.g.
    ``lambda p: jax.tree.map(lambda x: x.ndim >= 2, p)``.
    `clip_norm` prepends global-norm gradient clipping.  `b1`/`b2`
    default to each optimizer's own published defaults (adam/adamw
    0.9/0.999, lion 0.9/0.99).  Optimizers without a weight-decay knob
    (adam, sgd, adafactor) refuse a nonzero `weight_decay` rather than
    silently dropping it.

    `mu_dtype` (adam/adamw/lion and the fused variants) stores the first
    moment in a narrower dtype — ``"bfloat16"`` halves that state's HBM
    footprint AND the optimizer update's bandwidth (momentum is
    noise-tolerant; the second moment stays float32).  Its effect on
    the flagship step time on this chip: not measured.

    ``adamw_fused`` / ``lion_fused`` run the whole update — clip scale,
    moments, decay, lr — as ONE Pallas pass per parameter block
    (ops/fused_optim.py): `clip_norm` folds in as a pre-computed scalar
    instead of a chained transform, and the returned object carries an
    extra single-pass ``apply(grads, state, params)`` the train-step
    harness uses automatically.  Same math as the optax references
    (tests assert step-for-step parity); fewest HBM passes of any
    optimizer here — the SPEED choice, vs adamw8bit (memory).
    """
    import optax

    if isinstance(mu_dtype, str):
        import jax.numpy as jnp
        mu_dtype = jnp.dtype(mu_dtype)
    if mu_dtype is not None and name not in ("adam", "adamw", "lion") + _FUSED:
        raise ValueError(f"optimizer={name!r} has no mu_dtype knob")
    if layouts is not None and name != "adamw8bit":
        raise ValueError(
            f"optimizer={name!r} has no quantized-state layouts knob "
            "(layouts= is adamw8bit-only; see optim8bit.layouts_for_shardings)")

    if name not in OPTIMIZERS:
        raise ValueError(f"optimizer={name!r} not in {OPTIMIZERS}")
    if (weight_decay or decay_mask is not None) and name not in (
            "adamw", "adamw8bit", "lion") + _FUSED:
        raise ValueError(
            f"optimizer={name!r} has no decoupled weight decay; use adamw, "
            "adamw_fused, adamw8bit, or lion (or drop "
            "weight_decay/decay_mask)")
    sched = make_schedule(learning_rate, schedule, warmup_steps,
                          total_steps, end_value)
    if name == "adam":
        core = optax.adam(sched, b1=b1 or 0.9, b2=b2 or 0.999,
                          mu_dtype=mu_dtype)
    elif name == "adamw":
        core = optax.adamw(sched, b1=b1 or 0.9, b2=b2 or 0.999,
                           weight_decay=weight_decay, mask=decay_mask,
                           mu_dtype=mu_dtype)
    elif name in _FUSED:
        # single-pass Pallas kernels: clip_norm and decay fold INTO the
        # fused update (chaining optax.clip around them would both waste
        # a pass and strip the .apply method the train step fuses on)
        from tensorflowonspark_tpu.ops import fused_optim
        if name == "adamw_fused":
            core = fused_optim.adamw_fused(
                sched, b1=b1 or 0.9, b2=b2 or 0.999,
                weight_decay=weight_decay, mask=decay_mask,
                clip_norm=clip_norm, mu_dtype=mu_dtype)
        else:
            core = fused_optim.lion_fused(
                sched, b1=b1 or 0.9, b2=b2 or 0.99,
                weight_decay=weight_decay, mask=decay_mask,
                clip_norm=clip_norm, mu_dtype=mu_dtype)
    elif name == "adamw8bit":
        # int8 blockwise moments — 4x less optimizer HBM and update
        # bandwidth than f32 adamw (see optim8bit module doc); mu_dtype
        # is rejected above (the state is already 8-bit)
        from tensorflowonspark_tpu import optim8bit
        core = optim8bit.adamw8bit(sched, b1=b1 or 0.9, b2=b2 or 0.999,
                                   weight_decay=weight_decay,
                                   mask=decay_mask, layouts=layouts)
    elif name == "sgd":
        core = optax.sgd(sched, momentum=momentum)
    elif name == "lion":
        core = optax.lion(sched, b1=b1 or 0.9, b2=b2 or 0.99,
                          weight_decay=weight_decay, mask=decay_mask,
                          mu_dtype=mu_dtype)
    else:  # adafactor: the memory-frugal choice for big models
        core = optax.adafactor(sched)
    if clip_norm and name not in _FUSED:
        core = optax.chain(optax.clip_by_global_norm(clip_norm), core)
    logger.info("optimizer %s lr=%s schedule=%s warmup=%d wd=%s clip=%s",
                name, learning_rate, schedule, warmup_steps, weight_decay,
                clip_norm)
    return core, sched


def default_decay_mask(params):
    """True (decay) for >=2-D kernels, False for biases/norm scales."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: getattr(x, "ndim", 0) >= 2, params)
