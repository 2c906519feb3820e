"""decode_step_ms.steady: see chipbench/readers.py:decode_step_ms."""
from chipbench.readers import decode_step_ms as read  # noqa: F401
