"""kcp_tpu.obs — fleet-wide distributed tracing (see obs/trace.py)."""

from .trace import (
    NOOP,
    PERF_TO_MONO,
    PHASES,
    TRACEPARENT,
    TRACER,
    TraceContext,
    annotate,
    conv_begin,
    ctx_from_wal,
    current,
    edge_append,
    edge_kept,
    edges,
    link_obj,
    obj_link,
    phase,
    record_span,
    reset_current,
    set_current,
    span,
    use,
    write_ctx,
)

__all__ = [
    "NOOP", "PERF_TO_MONO", "PHASES", "TRACEPARENT", "TRACER",
    "TraceContext", "annotate", "conv_begin",
    "ctx_from_wal", "current", "edge_append", "edge_kept", "edges",
    "link_obj", "obj_link", "phase",
    "record_span", "reset_current", "set_current", "span", "use",
    "write_ctx",
]
