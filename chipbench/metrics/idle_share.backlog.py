"""1 - (union of the device's op intervals) / traced window."""
from chipbench.readers import idle_share as read  # noqa: F401
