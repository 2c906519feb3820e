"""decode_ssm_state_ms.steady: see chipbench/ssm.py:decode_ssm_state_ms."""
from chipbench.ssm import decode_ssm_state_ms as read  # noqa: F401
