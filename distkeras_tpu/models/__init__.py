"""Model zoo + Model abstraction (the framework's "Keras model" analogue)."""

from distkeras_tpu.models.base import (  # noqa: F401
    DKModule,
    Model,
    register_model,
)
from distkeras_tpu.models.mlp import MLP, mnist_mlp  # noqa: F401
from distkeras_tpu.models.cnn import SimpleCNN, mnist_cnn, cifar10_cnn  # noqa: F401
from distkeras_tpu.models.lstm import LSTMClassifier, imdb_lstm  # noqa: F401
from distkeras_tpu.models.resnet import ResNet, resnet50  # noqa: F401
from distkeras_tpu.models.transformer import TransformerLM, small_transformer_lm  # noqa: F401
from distkeras_tpu.models.smallthinker import SmallThinkerLM, small_smallthinker_lm  # noqa: F401
from distkeras_tpu.models.lfm2 import Lfm2MoeLM, small_lfm2_lm  # noqa: F401
from distkeras_tpu.models.kimi_linear import KimiLinearLM, small_kimi_linear_lm  # noqa: F401

__all__ = [
    "DKModule",
    "Model",
    "register_model",
    "MLP",
    "mnist_mlp",
    "SimpleCNN",
    "mnist_cnn",
    "cifar10_cnn",
    "LSTMClassifier",
    "imdb_lstm",
    "ResNet",
    "resnet50",
    "TransformerLM",
    "small_transformer_lm",
    "SmallThinkerLM",
    "small_smallthinker_lm",
    "Lfm2MoeLM",
    "small_lfm2_lm",
    "KimiLinearLM",
    "small_kimi_linear_lm",
]
