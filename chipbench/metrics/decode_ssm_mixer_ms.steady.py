"""decode_ssm_mixer_ms.steady: see chipbench/ssm.py:decode_ssm_mixer_ms."""
from chipbench.ssm import decode_ssm_mixer_ms as read  # noqa: F401
