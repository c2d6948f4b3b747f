"""round_ms.chat: see harness/spans.py, round_ms."""

from harness.spans import round_ms as read  # noqa: F401
