"""queue_wait_ms.steady: see chipbench/scopes.py:queue_wait_ms."""
from chipbench.scopes import queue_wait_ms as read  # noqa: F401
