"""dense_mcma: see chipbench/reference.py:Reference."""
from chipbench.reference import Reference  # noqa: F401
