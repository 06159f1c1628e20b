"""Per-layer metric frontend_host_ms.backlog (see readers.frontend_host_ms)."""
from readers import frontend_host_ms as read  # noqa: F401
