"""queue_wait_p90_ms.chat: see harness/readers.py, queue_wait_p90_ms."""

from harness.readers import queue_wait_p90_ms as read  # noqa: F401
