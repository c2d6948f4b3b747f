"""prefill_wait_p90_ms.chat: see harness/spans.py, prefill_wait_p90_ms."""

from harness.spans import prefill_wait_p90_ms as read  # noqa: F401
