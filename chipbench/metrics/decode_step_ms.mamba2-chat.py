"""Host milliseconds per decode step, until its logits are ready."""
from chipbench.readers import decode_step_ms as read  # noqa: F401
