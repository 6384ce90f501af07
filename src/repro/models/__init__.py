"""Model zoo: one unified period-structured LM stack covering all six
assigned families (dense / moe / vlm / hybrid / audio / ssm), plus the
paper's four FL-task models (repro.models.papertasks)."""

from repro.models.lm import (decode_step, forward, init_cache, init_params,
                             layer_plan, loss_and_counts, loss_fn,
                             param_count, prefill)

__all__ = ["init_params", "forward", "loss_fn", "loss_and_counts",
           "init_cache", "prefill",
           "decode_step", "layer_plan", "param_count", "make_loss_fn",
           "make_batch_spec"]


def make_loss_fn(cfg):
    """Bind the arch config: ``loss(params, batch) -> (loss, counts)`` for
    the FL engine's round programs, ``counts`` the model's per-step counts
    (the dropless expert layer's rows; empty for most architectures)."""

    def _loss(params, batch):
        return loss_and_counts(params, batch, cfg)

    return _loss


def make_batch_spec(cfg, *, batch: int, seq_len: int):
    """Host-side shapes/dtypes of one training micro-batch for this arch
    (smoke tests, the FL engine's synthetic federated data)."""
    import numpy as np

    spec = {"tokens": ((batch, seq_len), np.int32)}
    if cfg.frontend == "patch":
        spec["patch_embed"] = ((batch, cfg.frontend_len,
                                cfg.resolved_frontend_dim), np.float32)
    if cfg.frontend == "audio":
        spec["frames"] = ((batch, cfg.frontend_len, cfg.d_model), np.float32)
    return spec
