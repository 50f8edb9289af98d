"""Model zoo (parity: python/mxnet/gluon/model_zoo/)."""
from . import language, vision
from .vision import get_model  # noqa: F401
