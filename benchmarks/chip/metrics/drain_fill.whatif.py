"""Per-layer metric drain_fill.whatif (see program_trace.drain_fill)."""
from program_trace import drain_fill as read  # noqa: F401
