from repro.configs.base import FLConfig, ModelConfig  # noqa: F401
from repro.configs.registry import ARCHS, get_config  # noqa: F401
