"""Per-layer metric scan_device_us_per_event.whatif (see readers.scan_device_us_per_event)."""
from readers import scan_device_us_per_event as read  # noqa: F401
