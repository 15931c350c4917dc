"""Train-side package. Lazy (PEP 562) like ``edl_tpu.data``/``distill``:
``import edl_tpu.train`` must not pull jax/flax, so jax-free consumers
(the chaos plane's checkpoint drills via ``train.ckpt_io``) can import
the package on a box with no accelerator stack."""

_LAZY = {
    "TrainState": ("edl_tpu.train.state", "TrainState"),
    "TrainStatus": ("edl_tpu.train.state", "TrainStatus"),
    "CheckpointManager": ("edl_tpu.train.checkpoint", "CheckpointManager"),
    "CheckpointWriteError": ("edl_tpu.train.checkpoint",
                             "CheckpointWriteError"),
    "lr": ("edl_tpu.train.lr", None),
}

__all__ = list(_LAZY)


def __getattr__(name: str):
    try:
        module_name, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(
            f"module 'edl_tpu.train' has no attribute {name!r}") from None
    import importlib
    module = importlib.import_module(module_name)
    value = module if attr is None else getattr(module, attr)
    globals()[name] = value
    return value
