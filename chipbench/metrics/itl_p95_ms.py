"""itl_p95_ms: see chipbench/readers.py:itl_p95_ms."""
from chipbench.readers import itl_p95_ms as read  # noqa: F401
