"""Per-layer metric screen_roofline.whatif (see readers.screen_roofline)."""
from readers import screen_roofline as read  # noqa: F401
