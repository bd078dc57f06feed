"""Optimizer construction from config.

Capability parity with the reference's ``runtime/engine.py:1473``
(_configure_basic_optimizer): the same ``optimizer.type`` names a reference
JSON uses (Adam/AdamW/FusedAdam variants, Lamb, Lion, SGD, Adagrad, Muon,
and the 1-bit family OnebitAdam/ZeroOneAdam/OnebitLamb — see
``runtime/onebit.py`` for the compressed-momentum update rules). The
reference's Fused*/CPU* names (FusedAdamBuilder etc., §2.13) are accepted as
names and build the same optax rule as the plain one on every backend: the
update is elementwise, XLA fuses it on each leaf's own shape and shard, and
the engine's donated train step makes it in place.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import optax

from ..config.config_utils import ConfigError
from ..profiling import trace
from ..utils.logging import log_dist

# type -> (factory, accepted param names)
_ADAM_DEFAULTS = dict(lr=1e-3, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0)


def _split_wd(params_fn: Optional[Callable] = None):
    return params_fn


def build_optimizer(optimizer_config, lr_schedule, gradient_clipping: float = 0.0,
                    weight_decay_mask: Optional[Any] = None) -> optax.GradientTransformation:
    """Build the optax chain: [clip_by_global_norm] -> update rule (lr = schedule).

    Loss-scale unscaling and overflow skipping are handled by the engine
    around this transformation (they need the loss-scale state).
    """
    if optimizer_config is None:
        raise ConfigError("No optimizer section in config and no client optimizer provided")
    name = optimizer_config.type
    params = dict(optimizer_config.params)
    lr = params.pop("lr", params.pop("learning_rate", 1e-3))
    betas = params.pop("betas", (0.9, 0.999))
    b1, b2 = float(betas[0]), float(betas[1])
    eps = float(params.pop("eps", 1e-8))
    wd = float(params.pop("weight_decay", 0.0))
    momentum = float(params.pop("momentum", 0.0))
    schedule = lr_schedule if lr_schedule is not None else lr

    lowered = name.lower()
    if lowered in ("onebitadam", "zerooneadam", "onebitlamb"):
        from .onebit import onebit_adam, onebit_lamb, zero_one_adam

        freeze = int(params.pop("freeze_step", 100))
        if lowered == "onebitadam":
            tx = onebit_adam(schedule, b1=b1, b2=b2, eps=eps, weight_decay=wd,
                             freeze_step=freeze, mask=weight_decay_mask)
        elif lowered == "zerooneadam":
            tx = zero_one_adam(schedule, b1=b1, b2=b2, eps=eps, weight_decay=wd,
                               var_freeze_step=int(params.pop("var_freeze_step", freeze)),
                               var_update_scaler=int(params.pop("var_update_scaler", 16)),
                               local_step_clipper=int(params.pop("local_step_clipper", 32)),
                               mask=weight_decay_mask)
        else:
            tx = onebit_lamb(schedule, b1=b1, b2=b2, eps=eps, weight_decay=wd, freeze_step=freeze,
                             max_coeff=float(params.pop("max_coeff", 10.0)),
                             min_coeff=float(params.pop("min_coeff", 0.01)),
                             mask=weight_decay_mask)
    elif lowered in ("adam", "fusedadam", "cpuadam", "adamw"):
        # reference FusedAdam/DeepSpeedCPUAdam both default adam_w_mode=True
        adam_w_mode = params.pop("adam_w_mode", lowered in ("adamw", "fusedadam", "cpuadam"))
        if adam_w_mode or lowered == "adamw":
            tx = optax.adamw(schedule, b1=b1, b2=b2, eps=eps, weight_decay=wd, mask=weight_decay_mask)
        else:
            tx = optax.adam(schedule, b1=b1, b2=b2, eps=eps)
            if wd:
                tx = optax.chain(optax.add_decayed_weights(wd, mask=weight_decay_mask), tx)
    elif lowered in ("lamb", "fusedlamb"):
        tx = optax.lamb(schedule, b1=b1, b2=b2, eps=eps, weight_decay=wd, mask=weight_decay_mask)
    elif lowered in ("lion", "fusedlion", "cpulion"):
        tx = optax.lion(schedule, b1=b1, b2=b2, weight_decay=wd, mask=weight_decay_mask)
    elif lowered == "sgd":
        tx = optax.sgd(schedule, momentum=momentum if momentum else None,
                       nesterov=bool(params.pop("nesterov", False)))
        if wd:
            tx = optax.chain(optax.add_decayed_weights(wd, mask=weight_decay_mask), tx)
    elif lowered in ("adagrad", "cpuadagrad"):
        tx = optax.adagrad(schedule, eps=eps)
    elif lowered == "muon":
        # Muon (reference ops/muon): Newton-Schulz orthogonalized momentum.
        # optax ships a contrib implementation in recent versions.
        try:
            from optax import contrib as _contrib

            tx = _contrib.muon(schedule, beta=b1 or 0.95, weight_decay=wd)  # type: ignore[attr-defined]
        except (ImportError, AttributeError):
            log_dist("optax.contrib.muon unavailable; falling back to AdamW", ranks=[0])
            tx = optax.adamw(schedule, b1=b1, b2=b2, eps=eps, weight_decay=wd)
    else:
        raise ConfigError(f"Unknown optimizer type {name!r}")

    if params:
        log_dist(f"Optimizer {name}: ignoring unsupported params {sorted(params)}", ranks=[0])
    if gradient_clipping and gradient_clipping > 0:
        clip = optax.clip_by_global_norm(gradient_clipping)

        def clip_update(updates, state, params=None):
            with trace.scope("grad_clip"):
                return clip.update(updates, state, params)

        tx = optax.chain(optax.GradientTransformation(clip.init, clip_update), tx)
    return tx


def get_base_lr(optimizer_config) -> float:
    if optimizer_config is None:
        return 1e-3
    p = optimizer_config.params
    return float(p.get("lr", p.get("learning_rate", 1e-3)))


class DummyOptim:
    """Optimizer-less path marker (reference runtime/utils.py DummyOptim)."""
