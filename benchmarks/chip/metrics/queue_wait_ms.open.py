"""Per-layer metric queue_wait_ms.open (see program_trace.queue_wait_ms)."""
from program_trace import queue_wait_ms as read  # noqa: F401
