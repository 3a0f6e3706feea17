"""What this process runs on: the one platform probe and the one rank
probe.

Kernel dispatch ("auto" impls), the CLIs' exit report and
``chip_smoke.py`` all ask :func:`on_tpu` / :func:`device_facts`; a
backend that cannot start raises out of them instead of reading as
"not a TPU" — a chip that failed to open must stop the run, not send
it down the reference paths.

:func:`process_rank` is the other half of "one process per chip":
logging and metrics decorate records with the JAX process index, but
asking JAX for it *initialises* the backend, and a process that owns a
backend owns the chip.  A supervisor parent that logs one line between
two children would take the device from the child it is about to
spawn.  So the rank is read only once a backend already exists.
"""

from typing import Optional, Tuple


def on_tpu() -> bool:
    """True when JAX's first device is a TPU.  Initialises the backend;
    an initialisation error propagates."""
    import jax

    return jax.devices()[0].platform == "tpu"


def device_facts() -> dict:
    """The device as JAX reports it — stamped on every result a run is
    judged by."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def process_rank() -> Optional[Tuple[int, int]]:
    """``(process_index, process_count)`` if this process already holds
    a JAX backend, else None.  Never initialises one."""
    import jax
    from jax._src import xla_bridge  # no public "is a backend up?" probe

    if not xla_bridge.backends_are_initialized():
        return None
    return jax.process_index(), jax.process_count()


def device_memory(**trees) -> list:
    """Per local device: the allocator's ``bytes_in_use`` /
    ``peak_bytes_in_use`` (where the backend reports them) and, for each
    named pytree of arrays, the bytes of it resident on that device
    (summed over the array shards that live there, no transfer) beside
    its global size.  What shows that a sharded layout gave every chip
    its share and none the whole."""
    import jax

    rows = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        row = {"device": dev.id,
               "bytes_in_use": stats.get("bytes_in_use"),
               "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
        for name, tree in trees.items():
            leaves = jax.tree.leaves(tree)
            row[f"{name}_bytes"] = sum(
                shard.data.nbytes for leaf in leaves
                for shard in leaf.addressable_shards if shard.device == dev)
            row[f"{name}_global_bytes"] = sum(leaf.nbytes for leaf in leaves)
        rows.append(row)
    return rows
