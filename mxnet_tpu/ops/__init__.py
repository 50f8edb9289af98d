"""Operator registry + built-in operator library.

Importing this package registers all built-in ops (the reference's
src/operator/ static registration via NNVM_REGISTER_OP happens at library
load; here it happens at import).
"""
from . import registry
from .registry import Operator, get, exists, list_ops, register, register_simple

# built-in op library — import order irrelevant, names must be unique
from . import _op_tensor  # noqa: F401
from . import _op_nn  # noqa: F401
from . import _op_random  # noqa: F401
from . import _op_optimizer  # noqa: F401
from . import _op_linalg  # noqa: F401
from . import _op_contrib  # noqa: F401
from . import _op_quantization  # noqa: F401
from . import _op_image  # noqa: F401
from . import _op_spatial  # noqa: F401
from . import _op_ssm  # noqa: F401
from . import _op_linear_attention  # noqa: F401
from . import _op_moe  # noqa: F401
from . import pallas_attention  # noqa: F401
