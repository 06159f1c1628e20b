"""Per-layer metric idle_program_ms.open (see program_trace.idle_program_ms)."""
from program_trace import idle_program_ms as read  # noqa: F401
