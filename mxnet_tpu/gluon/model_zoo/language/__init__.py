"""Language model zoo: decoder-only sequence models built from
``HybridBlock``s with every size given at construction."""
from .granite import *  # noqa: F401,F403
from .granite import (GatedMLP, GraniteHybrid, GroupedQueryAttention,
                      HybridDecoderLayer, Mamba2Mixer, granite_hybrid)
