"""prefill_ssm_ms.steady: see chipbench/ssm.py:prefill_ssm_ms."""
from chipbench.ssm import prefill_ssm_ms as read  # noqa: F401
