"""kv_pages_held.steady: see chipbench/readers.py:kv_pages_held."""
from chipbench.readers import kv_pages_held as read  # noqa: F401
