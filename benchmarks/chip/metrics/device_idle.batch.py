"""Per-layer metric device_idle.batch (see readers.device_idle)."""
from readers import device_idle as read  # noqa: F401
