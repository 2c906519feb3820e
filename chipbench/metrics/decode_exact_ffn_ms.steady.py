"""decode_exact_ffn_ms.steady: see chipbench/scopes.py:decode_exact_ffn_ms."""
from chipbench.scopes import decode_exact_ffn_ms as read  # noqa: F401
