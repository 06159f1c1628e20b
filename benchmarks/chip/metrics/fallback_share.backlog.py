"""Per-layer metric fallback_share.backlog (see readers.fallback_share)."""
from readers import fallback_share as read  # noqa: F401
