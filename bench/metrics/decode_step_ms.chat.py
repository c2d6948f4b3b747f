"""decode_step_ms.chat: see harness/readers.py, decode_step_ms."""

from harness.readers import decode_step_ms as read  # noqa: F401
