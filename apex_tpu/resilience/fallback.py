"""Kernel fallback registry: degrade once instead of crashing the run.

Every Pallas entry point in this repo has an XLA reference
implementation that is the numerics specification (``ops/attention.py``
scan path for ``flash_attention_pallas``, the chunked scan in
``ops/fused_ce.py`` for the CE kernels, the jnp composite in
``normalization/fused_layer_norm.py`` for the LayerNorm kernels).  The
kernels have never been proven on real chips (VERDICT r5), so a Mosaic
lowering surprise must not kill a multi-hour training run that the
reference impl could have carried at reduced throughput.

The registry sits at each call-site seam:

    return get_registry().call("fused_ce", pallas_impl, scan_impl)

- the first failure of a kernel **trips** it: one structured warning
  (``kernel_fallback.tripped``) with the error, then the fallback runs
  — and every later trace of that kernel goes straight to the fallback
  with no further noise ("degrade once").
- the decision composes with the existing explicit ``impl=``-style
  config threading (``fused_ce_impl``, ``flash_attention(impl=...)``):
  when the impl is *chosen* (``auto``/platform default) the registry
  wraps the kernel; when the caller *forced* the kernel impl
  (``impl="pallas"``, ``fused_ce_impl="on"``) it bypasses the registry
  and failures surface loudly — a forced impl silently degrading to the
  reference would make every kernel-vs-oracle test and every
  pallas-vs-scan A/B vacuous (:func:`registry_engaged`).  The chaos
  harness re-engages the registry even for forced impls: CPU tests must
  force ``interpret`` to reach the kernel path at all, and the fallback
  seam is exactly what they exist to prove.  No env vars are consulted
  (the APX101/102 contract).
- the chaos harness injects launch failures through the same seam
  (:func:`apex_tpu.resilience.chaos.check_kernel` runs just before the
  kernel), so the fallback path tested on CPU is byte-for-byte the one
  hardware failures will take.

Scope caveat (documented, deliberate): the registry catches failures
that surface while the kernel's Python/trace-time code runs.  A Mosaic
error deferred to ``jit`` *compile* time surfaces to the caller of the
compiled step; catch it there, feed it to :func:`trip_from_exception`,
and rebuild the step — the new trace consults the registry and lowers
the fallback.  ``examples/gpt/pretrain_gpt.py`` wires this.

Collective-bearing engines NEVER register here.  (Nor do the fused
optimizers of :mod:`apex_tpu.optimizers`, for another reason: their
one route, the per-leaf update, is plain XLA ops, so there is nothing
to degrade from.)  The case the rule is for is the ZeRO bucket
engine: it
(:mod:`apex_tpu.contrib.optimizers._zero_engine`) has per-bucket
reduce-scatters and all-gathers INSIDE the optimizer, so a per-process
degrade-once would lower divergent SPMD programs across the pod —
mismatched collective counts deadlock every host device-side with no
error (the same invariant :func:`registry_engaged` enforces by
disengaging under ``jax.process_count() > 1``).  ZeRO therefore runs
its engine directly and fails fast; ``--auto-resume`` is the recovery
path.
"""

import dataclasses
import logging
import threading
from typing import Callable, Dict, List, Optional

from apex_tpu.observability import metrics as _metrics
from apex_tpu.utils.logging import get_logger, log_structured

__all__ = [
    "KERNELS", "KernelFallbackRegistry", "get_registry",
    "registry_engaged", "trip_from_exception",
]

_logger = get_logger("apex_tpu.resilience")

#: The registered Pallas entry points and the markers by which a
#: compile-time error message is attributed to one of them.  Markers are
#: kernel-SPECIFIC tokens (the ``*_pallas`` entry-point/module names and
#: the kernel-body def names) — never the bare op name: XLA runtime
#: errors embed HLO instruction names derived from the traced Python
#: functions, so an OOM or sharding error whose dump mentions
#: ``layer_norm`` must NOT be attributed as a kernel failure (the caller
#: would swallow the real error and burn a recompile per retry).  A
#: marker shared by several kernels' source (``_fwd_kernel`` is a def in
#: BOTH flash_attention_pallas.py and fused_ce_pallas.py) appears under
#: every owner: tripping both costs the innocent one throughput, while
#: tripping the wrong one alone would re-lower the broken kernel and
#: crash the retry.
KERNELS: Dict[str, tuple] = {
    "flash_attention": ("flash_attention_pallas", "flash_fwd_pallas",
                        "flash_bwd_pallas", "_fwd_kernel", "_dq_kernel",
                        "_dkv_kernel"),
    "fused_ce": ("fused_ce_pallas", "fused_ce_fwd_pallas",
                 "fused_ce_bwd_pallas", "_fwd_kernel", "_dx_kernel",
                 "_dembed_kernel"),
    "layer_norm": ("layer_norm_pallas", "_ln_fwd_kernel",
                   "_ln_bwd_kernel"),
    "decode_attention": ("decode_attention_pallas",
                         "paged_decode_attention_pallas",
                         "_decode_attn_kernel"),
    "kv_write": ("kv_write_pallas", "_kv_write_kernel"),
    "mla_decode_attention": ("mla_decode_pallas", "_mla_decode_kernel",
                             "_mla_walk_kernel"),
    # the recurrent-state kernels of ops/kda.py
    "kda_decode": ("_kda_decode_kernel",),
    "kda_conv_step": ("_kda_conv_step_kernel",),
    "kda_chunk_scan": ("_kda_chunk_scan_kernel",),
    "slot_install": ("_slot_install_kernel", "_slot_install_row_kernel"),
    # ops/ssd.py: the Mamba-2 state update (its chunked scan is XLA)
    "ssd_decode": ("_ssd_decode_kernel",),
    # ops/eva.py: the chunk summary; the prompt's attention rides the
    # flash forward, under a name of its own
    "eva_summarise": ("_summarise_kernel", "_summarise_pallas"),
    "eva_prefill_attention": ("_window_pallas",),
    "decode_sampling": ("decode_sampling_pallas", "fused_sample_pallas",
                        "_sample_kernel", "_merge_top_k"),
}


@dataclasses.dataclass
class _Entry:
    tripped: bool = False
    error: Optional[str] = None
    fallback_calls: int = 0
    kernel_calls: int = 0


class KernelFallbackRegistry:
    """Per-process record of which Pallas kernels are trusted."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[str, _Entry] = {k: _Entry() for k in KERNELS}

    def _entry(self, name: str) -> _Entry:
        with self._lock:
            return self._entries.setdefault(name, _Entry())

    # ------------------------------------------------------------- use
    def call(self, name: str, kernel: Callable[[], object],
             fallback: Callable[[], object]):
        """Run ``kernel()`` unless ``name`` is tripped; on failure trip
        it (one structured warning) and run ``fallback()``.

        Both callables are zero-arg closures so the registry never has
        to understand kernel signatures; they must return the same
        pytree structure (each kernel's fallback is its numerics
        specification, so this holds by construction)."""
        from apex_tpu.resilience import chaos

        e = self._entry(name)
        if e.tripped:
            with self._lock:
                e.fallback_calls += 1
            return fallback()
        try:
            chaos.check_kernel(name)
            out = kernel()
        except Exception as err:  # noqa: BLE001 — any kernel-path error
            # (injected launch failure, Mosaic lowering, interpret-mode
            # surprise) degrades to the reference impl; the error is
            # preserved in the warning and in status() for postmortems
            self.trip(name, err)
            with self._lock:
                e.fallback_calls += 1
            try:
                return fallback()
            except Exception:
                # the reference impl rejected the SAME call: the fault
                # is the arguments (e.g. a shape-validation error raised
                # inside the kernel closure), not the kernel — un-trip
                # so later valid calls still reach the kernel, and let
                # the fallback's (clearer) validation error surface
                log_structured(
                    _logger, logging.WARNING, "kernel_fallback.reset",
                    kernel=name,
                    reason="reference impl rejected the same call; "
                           "attributing the failure to the arguments")
                self.reset(name)
                raise
        with self._lock:
            e.kernel_calls += 1
        return out

    # ----------------------------------------------------------- state
    def trip(self, name: str, error) -> None:
        """Mark ``name`` failed; warn exactly once per trip."""
        e = self._entry(name)
        with self._lock:
            if e.tripped:
                return
            e.tripped = True
            e.error = f"{type(error).__name__}: {error}"
        log_structured(
            _logger, logging.WARNING, "kernel_fallback.tripped",
            kernel=name, error=e.error,
            action="using XLA reference impl for every later trace")
        _metrics.inc("apex_kernel_fallback_trips_total",
                     help="Pallas kernels degraded to their XLA reference",
                     kernel=name)

    def tripped(self, name: str) -> bool:
        return self._entry(name).tripped

    def reset(self, name: Optional[str] = None) -> None:
        """Forget trips (all kernels, or one).  Already-compiled jits
        keep whatever impl they traced; only NEW traces re-try the
        kernel."""
        with self._lock:
            names = [name] if name is not None else list(self._entries)
            for n in names:
                self._entries[n] = _Entry()

    def status(self) -> Dict[str, dict]:
        with self._lock:
            return {k: dataclasses.asdict(v)
                    for k, v in self._entries.items()}


_REGISTRY = KernelFallbackRegistry()


def get_registry() -> KernelFallbackRegistry:
    return _REGISTRY


def registry_engaged(forced: bool) -> bool:
    """Should a kernel call site route through the registry?

    ``forced`` means the caller explicitly pinned the kernel impl
    (``flash_attention(impl="pallas")``, ``fused_ce_impl="on"``): that
    is a demand to run THIS impl or fail loudly, so the registry stays
    out of the way — silently degrading a forced kernel to its reference
    would make kernel-vs-oracle tests pass vacuously and pallas-vs-scan
    benchmarks compare the reference against itself.  The chaos harness
    overrides: its CPU tests can only reach the kernel path by forcing
    ``interpret``, and the fallback seam is what they exist to prove.

    Multi-process runs NEVER engage the registry: a per-process degrade
    would lower the fallback's collective program (per-chunk scan psums)
    on the failing host while its peers lower the kernel's — mismatched
    collective counts deadlock every host device-side, with no error.
    Failing fast instead gives the clean job-level crash that
    ``--auto-resume`` restarts from (the same reasoning as the
    fail-fast multiproc rebuild path in examples/gpt/pretrain_gpt.py)."""
    import jax

    from apex_tpu.resilience.chaos import active_monkey
    from apex_tpu.resilience.uniformity import assert_uniform

    if jax.process_count() > 1:
        engaged = False
    else:
        engaged = (not forced) or active_monkey() is not None
    # record-only (no collective): every process must reach the same
    # engagement decision — a per-process degrade lowers mismatched
    # collective programs; check_uniform() surfaces the divergence as
    # a named error before the pod can wedge on it
    assert_uniform(f"kernel_registry.engaged/forced={bool(forced)}",
                   engaged)
    return engaged


def trip_from_exception(exc: BaseException) -> List[str]:
    """Attribute a deferred (jit-compile-time) kernel failure.

    Matches the exception text against each registered kernel's markers
    and trips the ones identified; returns the tripped names (empty when
    the error does not look like a Pallas/Mosaic kernel failure).  The
    caller then rebuilds/re-jits its step: the fresh trace consults the
    registry and lowers the XLA reference impl instead."""
    text = str(exc)
    lower = text.lower()
    # "mosaic" names the TPU kernel compiler and appears only in its
    # own failures; "pallas" is deliberately NOT a generic trigger — it
    # is the API name and shows up in innocent error text (module paths,
    # buffer names of successfully-compiled kernels inside an OOM dump),
    # and tripping every kernel on such an error would swallow the real
    # failure behind len(KERNELS)+1 recompiles (see the KERNELS note)
    generic = "mosaic" in lower
    # A runtime RESOURCE_EXHAUSTED (HBM OOM) names its allocations by op
    # metadata derived from the traced functions — including the
    # ``*_pallas`` entry-point names of kernels that compiled FINE — so
    # the marker match below would misattribute it.  Resource exhaustion
    # is not a lowering failure: unless Mosaic itself is named, nothing
    # trips and the real error surfaces to the caller immediately.
    if not generic and ("resource_exhausted" in lower
                        or "resource exhausted" in lower
                        or "out of memory" in lower):
        return []
    tripped: List[str] = []
    for name, markers in KERNELS.items():
        if any(m in text for m in markers):
            _REGISTRY.trip(name, exc)
            tripped.append(name)
    if not tripped and generic:
        # A Mosaic error we cannot attribute: trip every kernel rather
        # than crash the run on the next identical compile.
        for name in KERNELS:
            _REGISTRY.trip(name, exc)
            tripped.append(name)
    return tripped
