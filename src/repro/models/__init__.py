"""Model zoo shared by the FL layer (local training) and serving layer."""
from .config import (ArchConfig, EncoderConfig, InputShape, MLAConfig,
                     MoEConfig, RGLRUConfig, SSMConfig, INPUT_SHAPES,
                     TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
from .spec import (ParamSpec, init_from_specs, abstract_from_specs,
                   logical_axes, count_params)
from .transformer import (param_specs, cache_specs, forward_train, loss_fn,
                          prefill, decode_step, encode)
from .cnn import (cnn_specs, cnn_apply, cnn_apply_fast, cnn_loss,
                  cnn_loss_fast, cnn_accuracy, cnn_accuracy_fast,
                  cnn_correct_fast)

__all__ = [
    "ArchConfig", "EncoderConfig", "InputShape", "MLAConfig", "MoEConfig",
    "RGLRUConfig", "SSMConfig", "INPUT_SHAPES", "TRAIN_4K", "PREFILL_32K",
    "DECODE_32K", "LONG_500K",
    "ParamSpec", "init_from_specs", "abstract_from_specs", "logical_axes",
    "count_params",
    "param_specs", "cache_specs", "forward_train", "loss_fn", "prefill",
    "decode_step", "encode",
    "cnn_specs", "cnn_apply", "cnn_apply_fast", "cnn_loss", "cnn_loss_fast",
    "cnn_accuracy", "cnn_accuracy_fast", "cnn_correct_fast",
]
