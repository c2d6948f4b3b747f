"""kan_roofline.longdoc: see harness/readers.py, kan_roofline."""

from harness.readers import kan_roofline as read  # noqa: F401
