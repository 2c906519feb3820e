"""ttft_p95_ms: see chipbench/readers.py:ttft_p95_ms."""
from chipbench.readers import ttft_p95_ms as read  # noqa: F401
