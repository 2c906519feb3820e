"""served_invocation_rate.steady: see chipbench/readers.py:served_invocation_rate."""
from chipbench.readers import served_invocation_rate as read  # noqa: F401
