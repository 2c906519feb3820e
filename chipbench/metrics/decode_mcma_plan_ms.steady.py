"""decode_mcma_plan_ms.steady: see chipbench/scopes.py:decode_mcma_plan_ms."""
from chipbench.scopes import decode_mcma_plan_ms as read  # noqa: F401
