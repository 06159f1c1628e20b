"""Per-layer metric fallback_share.whatif (see program_trace.scan_fallback_share)."""
from program_trace import scan_fallback_share as read  # noqa: F401
