"""attn_roofline.chat: see harness/readers.py, attn_roofline."""

from harness.readers import attn_roofline as read  # noqa: F401
