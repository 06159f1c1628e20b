"""Per-layer metric screen_roofline.open (see readers.screen_roofline)."""
from readers import screen_roofline as read  # noqa: F401
