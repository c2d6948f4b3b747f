"""prefill_chunk_ms.longdoc: see harness/readers.py, prefill_chunk_ms."""

from harness.readers import prefill_chunk_ms as read  # noqa: F401
