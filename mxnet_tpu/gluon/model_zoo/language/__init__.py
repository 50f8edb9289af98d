"""Language model zoo: decoder-only sequence models built from
``HybridBlock``s with every size given at construction."""
from .granite import *  # noqa: F401,F403
from .granite import (GatedMLP, GraniteHybrid, GroupedQueryAttention,
                      HybridDecoderLayer, Mamba2Mixer, granite_hybrid)
from .solar_open2 import *  # noqa: F401,F403
from .solar_open2 import (KimiDeltaAttention, SolarDecoderLayer, SolarOpen2,
                          SparseExperts, solar_open2)
