"""Language model zoo: decoder-only sequence models built from
``HybridBlock``s with every size given at construction.  The blocks two or
more models share are in ``blocks``; each model file imports from it
alone."""
from .blocks import *  # noqa: F401,F403
from .blocks import (GatedMLP, GroupedQueryAttention, KimiDeltaAttention,
                     Mamba2Mixer, MultiHeadLatentAttention, Relu2MLP,
                     SparseExperts, balanced_bias)
from .granite import *  # noqa: F401,F403
from .granite import GraniteHybrid, HybridDecoderLayer, granite_hybrid
from .solar_open2 import *  # noqa: F401,F403
from .solar_open2 import SolarDecoderLayer, SolarOpen2, solar_open2
from .nemotron_h import *  # noqa: F401,F403
from .nemotron_h import NemotronH, NemotronLayer, nemotron_h
from .sdar_moe import *  # noqa: F401,F403
from .sdar_moe import SDARDecoderLayer, SDARMoE, sdar_moe
from .zaya import *  # noqa: F401,F403
from .zaya import (CompressedConvAttention, Zaya, ZayaDecoderLayer,
                   ZayaRouter, zaya)
from .kimi_linear import *  # noqa: F401,F403
from .kimi_linear import KimiDecoderLayer, KimiLinear, kimi_linear
