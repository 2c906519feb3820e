"""device_idle_frac.steady: see chipbench/readers.py:device_idle_frac."""
from chipbench.readers import device_idle_frac as read  # noqa: F401
