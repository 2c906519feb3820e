"""decode_kv_ms.steady: see chipbench/scopes.py:decode_kv_ms."""
from chipbench.scopes import decode_kv_ms as read  # noqa: F401
