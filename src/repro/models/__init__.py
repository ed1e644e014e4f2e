from .config import LayerSpec, MLAConfig, MoEConfig, ModelConfig, SSMConfig, \
    YaRNConfig
from .model import Model, cross_entropy
from .params import PSpec, unzip, zip_axes
