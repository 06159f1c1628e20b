"""Per-layer metric drain_device_ms.open (see readers.drain_device_ms)."""
from readers import drain_device_ms as read  # noqa: F401
