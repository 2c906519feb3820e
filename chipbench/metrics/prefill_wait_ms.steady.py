"""prefill_wait_ms.steady: see chipbench/scopes.py:prefill_wait_ms."""
from chipbench.scopes import prefill_wait_ms as read  # noqa: F401
