"""Model operations in the traced window over its length times the bf16
peak, in percent."""
from chipbench.readers import mfu as read  # noqa: F401
