from .config import ModelConfig
from .registry import build_model, serving_init

__all__ = ["ModelConfig", "build_model", "serving_init"]
