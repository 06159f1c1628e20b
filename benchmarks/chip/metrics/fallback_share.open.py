"""Per-layer metric fallback_share.open (see readers.fallback_share)."""
from readers import fallback_share as read  # noqa: F401
