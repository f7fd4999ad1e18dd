"""deepspeed_tpu — a TPU-native large-model training framework.

Provides the capability surface of DeepSpeed (reference: deepspeed/__init__.py:64
``initialize`` and :269 ``init_inference``) re-designed for JAX/XLA on TPU:

- ``initialize()`` returns a :class:`~deepspeed_tpu.runtime.engine.DeepSpeedEngine`
  that compiles a pure train step under ``jax.jit`` with explicit shardings over a
  named device mesh instead of wrapping an ``nn.Module`` with autograd hooks.
- ZeRO stages 1/2/3 are sharding policies over the parameter/gradient/optimizer
  pytrees (XLA inserts the all-gather / reduce-scatter collectives the reference
  issues by hand).
- Pipeline/tensor/expert/sequence parallelism are mesh axes, not process groups.
"""

from deepspeed_tpu.version import __version__, __version_info__

from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu import comm  # noqa: F401  (deepspeed.comm facade)
from deepspeed_tpu import zero  # noqa: F401  (deepspeed.zero API surface)


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mesh=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None,
               mpu=None):
    """Create a training engine (reference: deepspeed/__init__.py:64).

    Args:
        args: optional namespace carrying ``deepspeed_config`` (CLI compat).
        model: a model description — either a :class:`deepspeed_tpu.models.Model`
            (apply/init pair) or anything exposing ``init(rng)`` / ``apply``.
        optimizer: optional optax gradient transformation overriding the config's
            ``optimizer`` section (reference lets a client torch optimizer through).
        model_parameters: optional pre-initialised parameter pytree.
        training_data: optional dataset for engine-built input pipeline.
        lr_scheduler: optional optax schedule overriding the config's ``scheduler``.
        mesh: optional ``jax.sharding.Mesh``; default mesh is built from the config's
            parallel-dimension keys and ``jax.devices()``.
        config: dict or path to a DeepSpeed-style JSON config.

    Returns:
        tuple of (engine, optimizer_handle, dataloader, lr_scheduler_handle) to
        mirror the reference's 4-tuple return.
    """
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    if config is None and config_params is not None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    if config is None:
        raise ValueError("deepspeed_tpu.initialize: a config dict or path is required")

    comm.init_distributed(dist_init_required=dist_init_required)

    engine = DeepSpeedEngine(
        config=config,
        model=model,
        optimizer=optimizer,
        model_parameters=model_parameters,
        training_data=training_data,
        lr_scheduler=lr_scheduler,
        mesh=mesh,
        collate_fn=collate_fn,
        mpu=mpu,
    )
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, **kwargs):
    """Create an inference engine (reference: deepspeed/__init__.py:269)."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    if config is None:
        config = kwargs
    elif kwargs:
        config = {**config, **kwargs}
    cfg = DeepSpeedInferenceConfig(**config) if isinstance(config, dict) else config
    return InferenceEngine(model, cfg)


def add_config_arguments(parser):
    """Add ``--deepspeed`` / ``--deepspeed_config`` CLI args (reference:
    deepspeed/__init__.py:205)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag, no-op)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to DeepSpeed-style JSON config")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse_suppress())
    return parser


def argparse_suppress():
    import argparse
    return argparse.SUPPRESS
