"""setup_s: see chipbench/readers.py:setup_s."""
from chipbench.readers import setup_s as read  # noqa: F401
