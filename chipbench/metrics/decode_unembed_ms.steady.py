"""decode_unembed_ms.steady: see chipbench/scopes.py:decode_unembed_ms."""
from chipbench.scopes import decode_unembed_ms as read  # noqa: F401
