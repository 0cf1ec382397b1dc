"""apex_tpu — a TPU-native re-design of NVIDIA Apex (reference: /root/reference).

A standalone JAX/XLA/Pallas framework providing Apex's user-facing surface —
``amp.initialize`` O0–O3, ``amp.scale_loss``, ``parallel.DistributedDataParallel``,
``SyncBatchNorm``, the ``Fused*`` optimizers, ``FusedLayerNorm``, ``MLP`` and the
``multi_tensor_*`` suite — built TPU-first: pure jitted step functions, dtype
policies applied at trace time, collectives as mesh ops over ICI, and Pallas
kernels where fusion matters.

Mirrors apex/__init__.py:1-20 eager subpackage imports.
"""

from . import compat  # noqa: F401  (names jax's shard_map / axis_size)
from . import ops  # noqa: F401  (kernel substrate; the "amp_C" equivalent)
from . import multi_tensor_apply  # noqa: F401

__version__ = "0.1.0"

# Eager subpackage imports, mirroring the reference's `import apex` surface.
from . import amp  # noqa: F401,E402
from . import optimizers  # noqa: F401,E402
from . import nn  # noqa: F401,E402
from . import normalization  # noqa: F401,E402
from . import parallel  # noqa: F401,E402
from . import fp16_utils  # noqa: F401,E402
from . import mlp  # noqa: F401,E402
from . import pyprof  # noqa: F401,E402
