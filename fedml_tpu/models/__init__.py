"""Flax model zoo: TPU-native re-designs of the reference's PyTorch models
(``fedml_api/model/``). All modules are NHWC (TPU-preferred layout) and return
logits; losses live in the TrainSpec layer so every model composes with every
FL algorithm.
"""

from fedml_tpu.models.linear import LogisticRegression  # noqa: F401
from fedml_tpu.models.cnn import CNNOriginalFedAvg, CNNDropOut  # noqa: F401
from fedml_tpu.models.resnet import CifarResNet, resnet56, resnet110  # noqa: F401
from fedml_tpu.models.resnet_gn import ResNetGN, resnet18_gn, resnet34_gn, resnet50_gn  # noqa: F401
from fedml_tpu.models.mobilenet import MobileNet  # noqa: F401
from fedml_tpu.models.mobilenet_v3 import MobileNetV3  # noqa: F401
from fedml_tpu.models.efficientnet import EfficientNet, efficientnet  # noqa: F401
from fedml_tpu.models.vgg import VGG, vgg11, vgg13, vgg16, vgg19  # noqa: F401
from fedml_tpu.models.rnn import RNNOriginalFedAvg, RNNStackOverflow  # noqa: F401
from fedml_tpu.models.transformer import TransformerLM, transformer_nwp  # noqa: F401
from fedml_tpu.models.moe import MoEBlock, MoEMLP, MoETransformerLM  # noqa: F401
from fedml_tpu.models import deepseek_v3  # noqa: F401
from fedml_tpu.models.deepseek_v3 import DecoderConfig, DeepseekV3LM  # noqa: F401
from fedml_tpu.models.gkt import (  # noqa: F401
    GKTClientResNet, GKTServerResNet, resnet5_56, resnet8_56, resnet56_server)
from fedml_tpu.models.linear import DenseModel, LocalModel  # noqa: F401
from fedml_tpu.models.darts import (  # noqa: F401
    DARTSNetwork, DARTSFixedNetwork, Genotype, DARTS_V1, derive_genotype)
from fedml_tpu.models.factory import create_model  # noqa: F401
