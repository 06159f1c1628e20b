"""Per-layer metric compiles_in_window.batch (see readers.compiles_in_window)."""
from readers import compiles_in_window as read  # noqa: F401
