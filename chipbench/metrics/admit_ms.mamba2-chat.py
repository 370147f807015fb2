"""Host milliseconds in ServingEngine._admit per admitted request."""
from chipbench.readers import admit_ms as read  # noqa: F401
