"""Per-layer metric many_device_us.batch (see readers.many_device_us)."""
from readers import many_device_us as read  # noqa: F401
