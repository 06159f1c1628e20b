"""Per-layer metric stage2_device_ms.batch (see program_trace.stage2_device_ms)."""
from program_trace import stage2_device_ms as read  # noqa: F401
