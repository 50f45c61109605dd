"""``mx.gluon.model_zoo`` (gluon/model_zoo parity)."""
from . import text
from . import vision
from .vision import get_model

__all__ = ["text", "vision", "get_model"]
