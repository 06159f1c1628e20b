"""Per-layer metric collective_us.batch (see readers.collective_us)."""
from readers import collective_us as read  # noqa: F401
