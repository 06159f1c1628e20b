"""Per-layer metric frontend_host_ms.batch (see readers.batch_host_ms)."""
from readers import batch_host_ms as read  # noqa: F401
