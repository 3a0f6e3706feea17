"""Flagship models (reference: ``apex/transformer/testing/standalone_*.py``
and ``examples/imagenet``).

Served families (docs/inference.md), each a module that brings its
served-model adapter: ``gpt`` (also trained), ``mla_moe`` (latent
attention, held experts; with KDA layers ``kimi_linear``), ``evabyte``
(a windowed cache), ``afmoe`` (trained only), ``falcon_h1`` (attention
and a state-space mixer in every layer), ``sdar_moe`` (the sixth:
generation by diffusion over blocks) and ``lfm2_moe`` (the seventh:
gated short convolutions beside grouped attention, every expert held).
They are imported where they are used: ``import apex_tpu.models`` loads
``gpt`` alone."""

from apex_tpu.models import gpt

__all__ = ["gpt", "t5"]


def __getattr__(name):
    if name in ("resnet", "bert", "t5"):
        import importlib

        mod = importlib.import_module(f"apex_tpu.models.{name}")
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'apex_tpu.models' has no attribute {name!r}")
