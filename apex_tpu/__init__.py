"""apex_tpu — a TPU-native training-accelerator library.

A ground-up JAX/XLA/Pallas re-design of the capability surface of NVIDIA
Apex (reference: ``timmoon10/apex``; see ``/root/reference/apex/__init__.py``):

- :mod:`apex_tpu.amp` — mixed precision (O0–O3 dtype policies, device-side
  dynamic loss scaling with hysteresis).  Reference: ``apex/amp``.
- :mod:`apex_tpu.optimizers` — fused optimizers (Adam, LAMB, SGD, NovoGrad,
  Adagrad) with exact reference numerics.  Reference: ``apex/optimizers``.
- :mod:`apex_tpu.normalization` — fused LayerNorm/RMSNorm (Pallas kernels).
  Reference: ``apex/normalization``.
- :mod:`apex_tpu.parallel` — data parallelism (psum-DDP semantics, SyncBN,
  LARC).  Reference: ``apex/parallel``.
- :mod:`apex_tpu.transformer` — Megatron-style tensor/sequence/pipeline
  parallelism over ``jax.sharding.Mesh`` axes.  Reference:
  ``apex/transformer``.
- :mod:`apex_tpu.contrib` — optional extensions (xentropy, clip_grad,
  flash attention, group norm, ...).  Reference: ``apex/contrib``.

Unlike the reference, which accelerates PyTorch via CUDA extensions, this
library is functional-first: state lives in pytrees, transforms compose with
``jax.jit``/``jax.grad``/``jax.shard_map``, and multi-device execution uses
XLA collectives over a device mesh (ICI/DCN) instead of NCCL process groups.
"""

import logging as _logging

__version__ = "0.1.0"

from apex_tpu.utils.logging import RankInfoFormatter, get_logger

# Subpackages are imported lazily to keep `import apex_tpu` cheap and to
# avoid importing optional deps at package-import time (mirrors the lazy
# import structure of apex/__init__.py:20-30).
_LAZY_SUBMODULES = (
    "amp",
    "analysis",
    "optimizers",
    "normalization",
    "multi_tensor_apply",
    "fused_dense",
    "mlp",
    "parallel",
    "resilience",
    "transformer",
    "contrib",
    "models",
    "ops",
    "utils",
    "fp16_utils",
)


def __getattr__(name):
    if name in _LAZY_SUBMODULES:
        import importlib

        mod = importlib.import_module(f"apex_tpu.{name}")
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'apex_tpu' has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_LAZY_SUBMODULES))


def deprecated_warning(msg: str) -> None:
    """Emit a deprecation warning once (reference: apex/__init__.py:61)."""
    import warnings

    warnings.warn(msg, DeprecationWarning, stacklevel=2)
