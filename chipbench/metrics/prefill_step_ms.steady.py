"""prefill_step_ms.steady: see chipbench/readers.py:prefill_step_ms."""
from chipbench.readers import prefill_step_ms as read  # noqa: F401
