"""idle_share.longdoc: see harness/readers.py, idle_share."""

from harness.readers import idle_share as read  # noqa: F401
