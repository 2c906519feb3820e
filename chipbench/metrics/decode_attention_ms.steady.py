"""decode_attention_ms.steady: see chipbench/scopes.py:decode_attention_ms."""
from chipbench.scopes import decode_attention_ms as read  # noqa: F401
