"""ssm_state_roofline.steady: see chipbench/ssm.py:ssm_state_roofline."""
from chipbench.ssm import ssm_state_roofline as read  # noqa: F401
