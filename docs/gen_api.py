"""Generate docs/api.md from live introspection.

    JAX_PLATFORMS=cpu python docs/gen_api.py

Walks the public modules, lists every ``__all__`` (or public-attr) name
with the first line of its docstring — so the doc cannot drift from the
code without rerunning this.
"""

import importlib
import inspect
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

MODULES = [
    "apex_tpu",
    "apex_tpu.amp",
    "apex_tpu.fp16_utils",
    "apex_tpu.optimizers",
    "apex_tpu.normalization",
    "apex_tpu.fused_dense",
    "apex_tpu.mlp",
    "apex_tpu.RNN",
    "apex_tpu.parallel",
    "apex_tpu.multi_tensor_apply",
    "apex_tpu.ops.multi_tensor",
    "apex_tpu.ops.attention",
    "apex_tpu.transformer",
    "apex_tpu.transformer.parallel_state",
    "apex_tpu.transformer.tensor_parallel",
    "apex_tpu.transformer.pipeline_parallel",
    "apex_tpu.transformer.pipeline_parallel.p2p_communication",
    "apex_tpu.transformer.pipeline_parallel.utils",
    "apex_tpu.transformer.functional",
    "apex_tpu.transformer.layers",
    "apex_tpu.transformer.amp",
    "apex_tpu.transformer.enums",
    "apex_tpu.transformer.microbatches",
    "apex_tpu.transformer.utils",
    "apex_tpu.transformer.context_parallel",
    "apex_tpu.transformer.expert_parallel",
    "apex_tpu.transformer.testing",
    "apex_tpu.contrib.optimizers",
    "apex_tpu.contrib.fmha",
    "apex_tpu.contrib.multihead_attn",
    "apex_tpu.contrib.xentropy",
    "apex_tpu.contrib.focal_loss",
    "apex_tpu.contrib.transducer",
    "apex_tpu.contrib.clip_grad",
    "apex_tpu.contrib.group_norm",
    "apex_tpu.contrib.groupbn",
    "apex_tpu.contrib.cudnn_gbn",
    "apex_tpu.contrib.layer_norm",
    "apex_tpu.contrib.index_mul_2d",
    "apex_tpu.contrib.conv_bias_relu",
    "apex_tpu.contrib.sparsity",
    "apex_tpu.contrib.bottleneck",
    "apex_tpu.contrib.peer_memory",
    "apex_tpu.contrib.openfold_triton",
    "apex_tpu.models",
    "apex_tpu.io",
    "apex_tpu.utils",
]


def first_line(obj):
    doc = inspect.getdoc(obj)
    if not doc:
        return ""
    line = doc.strip().split("\n")[0].strip()
    if " object at 0x" in line:  # auto-generated repr docstrings are
        return ""                # non-reproducible (memory addresses)
    return line.replace("|", "\\|")


def public_names(mod):
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    names = []
    for n in sorted(vars(mod)):
        if n.startswith("_"):
            continue
        obj = getattr(mod, n)
        if inspect.ismodule(obj):
            continue
        owner = getattr(obj, "__module__", None)
        if owner is not None and not owner.startswith("apex_tpu"):
            continue  # imported name (typing.Optional, jnp, ...), not API
        if owner is None and not n.isupper():
            continue  # plain values: keep only UPPER_CASE constants
        names.append(n)
    return names


def discovered_modules():
    """Walk the package so a new public module cannot be silently
    missing from the doc; curated MODULES order first, discoveries
    appended sorted."""
    import pkgutil

    import apex_tpu

    found = set(MODULES)
    extra = []
    for info in pkgutil.walk_packages(apex_tpu.__path__, prefix="apex_tpu."):
        name = info.name
        if any(seg.startswith("_") for seg in name.split(".")):
            continue
        if name in found:
            continue
        try:
            mod = importlib.import_module(name)
        except Exception:  # noqa: BLE001 — optional/backend-gated modules
            continue
        if public_names(mod):
            extra.append(name)
            found.add(name)
    return MODULES + sorted(extra)


def main():
    out = [
        "# API reference",
        "",
        "Generated by `python docs/gen_api.py` — regenerate after changing",
        "any public surface.  One row per public name; the summary is the",
        "first docstring line, so this file tracks the code.",
        "",
    ]
    for modname in discovered_modules():
        mod = importlib.import_module(modname)
        names = public_names(mod)
        if not names:
            continue
        out.append(f"## `{modname}`")
        if mod.__doc__:
            out.append("")
            out.append(mod.__doc__.strip().split("\n")[0])
        out.append("")
        out.append("| Name | Kind | Summary |")
        out.append("|---|---|---|")
        for n in names:
            try:
                obj = getattr(mod, n)
            except Exception as e:  # noqa: BLE001 — surface import bugs in the doc
                out.append(f"| `{n}` | ? | (unresolvable: {e}) |")
                continue
            kind = (
                "class" if inspect.isclass(obj)
                else "function" if callable(obj)
                else type(obj).__name__
            )
            summary = first_line(obj) if (inspect.isclass(obj) or callable(obj)) else ""
            out.append(f"| `{n}` | {kind} | {summary} |")
        out.append("")
    path = Path(__file__).parent / "api.md"
    path.write_text("\n".join(out) + "\n")
    print(f"wrote {path} ({len(out)} lines)")


if __name__ == "__main__":
    main()
