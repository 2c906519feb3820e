"""decode_switch_kernel_ms.steady: see chipbench/scopes.py:decode_switch_kernel_ms."""
from chipbench.scopes import decode_switch_kernel_ms as read  # noqa: F401
