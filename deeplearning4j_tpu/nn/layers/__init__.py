"""Layer implementations (pure JAX, registry-keyed by config class name).

Importing this package registers every implementation; ``impl_for`` resolves a
config dataclass to its runtime impl (the TPU-native analog of the reference's
``Layer.instantiate`` dispatch in ``nn/conf/layers/*.java``).
"""
from .base import LayerImpl, NoParamLayerImpl, impl_for, implements  # noqa: F401
from . import feedforward  # noqa: F401
from . import convolution  # noqa: F401
from . import pooling  # noqa: F401
from . import normalization  # noqa: F401
from . import recurrent  # noqa: F401
from . import output  # noqa: F401
from . import variational  # noqa: F401
from . import objdetect  # noqa: F401
from . import attention  # noqa: F401
from . import moe  # noqa: F401
from . import kda  # noqa: F401
from . import looped  # noqa: F401
from . import mamba  # noqa: F401
from . import hybrid  # noqa: F401
from . import wrapper  # noqa: F401
