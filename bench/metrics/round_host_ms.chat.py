"""round_host_ms.chat: see harness/spans.py, round_host_ms."""

from harness.spans import round_host_ms as read  # noqa: F401
