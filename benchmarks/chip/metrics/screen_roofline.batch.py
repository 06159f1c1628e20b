"""Per-layer metric screen_roofline.batch (see readers.sharded_screen_roofline)."""
from readers import sharded_screen_roofline as read  # noqa: F401
