"""kcp_tpu.obs — fleet-wide distributed tracing (see obs/trace.py)."""

from .trace import (
    NOOP,
    PHASES,
    TRACEPARENT,
    TRACER,
    TraceContext,
    annotate,
    conv_begin,
    ctx_from_wal,
    current,
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
    "NOOP", "PHASES", "TRACEPARENT", "TRACER", "TraceContext", "annotate",
    "conv_begin",
    "ctx_from_wal", "current", "link_obj", "obj_link", "phase",
    "record_span", "reset_current", "set_current", "span", "use",
    "write_ctx",
]
