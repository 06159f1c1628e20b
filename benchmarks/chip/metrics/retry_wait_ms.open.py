"""Per-layer metric retry_wait_ms.open (see program_trace.retry_wait_ms)."""
from program_trace import retry_wait_ms as read  # noqa: F401
