from .common import ModelConfig, Params, cross_entropy_loss  # noqa: F401
from .model_zoo import Model, build_model  # noqa: F401
from .attention import MaskSpec, attend  # noqa: F401
