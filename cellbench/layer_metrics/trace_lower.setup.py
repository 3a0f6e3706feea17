"""Seconds of set-up spent tracing and lowering programs
(``setup_readers.trace_lower_s``), from the compile spans in the
program's own buffer: set-up lies before the traced stretch."""

from cellbench import setup_readers, span_readers


def read(ctx):
    from apex_tpu.observability import tracing

    tracer = tracing.get_tracer()
    return setup_readers.trace_lower_s(
        span_readers.program_spans(), ctx,
        tracer.dropped if tracer is not None else 0,
        getattr(tracer, "compile_errors", 0))
