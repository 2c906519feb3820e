"""tick_host_idle_ms.steady: see chipbench/scopes.py:tick_host_idle_ms."""
from chipbench.scopes import tick_host_idle_ms as read  # noqa: F401
