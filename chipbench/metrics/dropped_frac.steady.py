"""dropped_frac.steady: see chipbench/readers.py:dropped_frac."""
from chipbench.readers import dropped_frac as read  # noqa: F401
