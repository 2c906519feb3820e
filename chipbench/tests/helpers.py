"""Small configurations and mixes for the CPU tests: the cells' own
files with every size cut so that a run fits a test (Pallas kernels run
in the interpreter), and the norm epsilon set to the program's own
1e-6, so that the tests read the served path's arithmetic alone; the
cells' reference keeps the published epsilon."""
from __future__ import annotations

import copy
import time

from chipbench import run

PROGRAM_EPS = 1e-6
TINY_MODEL = {"n_layers": 2, "d_model": 128, "d_ff": 256, "vocab": 512}
# the cell's configuration, and a variant with the other attention and
# norm that the reference covers (MHA, LayerNorm, a quarter of each head
# rotated, biased q/k/v projections), for a cell added as a file alone
TINY_VARIANTS = {
    "internlm2-1.8b": {"n_heads": 4, "n_kv_heads": 2},
    "mha-layernorm": {"n_heads": 4, "n_kv_heads": 4, "norm": "layernorm",
                      "rope_pct": 0.25, "qkv_bias": True}}


def tiny_conf(name: str, *, limit: float = 0.05) -> dict:
    conf = copy.deepcopy(run.load_config("internlm2-1.8b"))
    conf["name"] = conf["model"]["name"] = name
    conf["model"].update(TINY_MODEL, **TINY_VARIANTS[name])
    conf["approx"].update(d_hidden=128)
    conf["serve"].update(batch=4, max_len=128, prefill_chunk=16)
    conf["check"]["max_logit_gap"] = limit
    for key in ("rms_norm_eps", "layer_norm_eps"):
        if key in conf:
            conf[key] = PROGRAM_EPS
    return conf


def tiny_spec() -> dict:
    return {"rate_per_s": 6.0,
            "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                       "min": 4, "max": 80},
            "output": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                       "min": 2, "max": 40},
            "block": 16, "warmup_s": 0.5, "check_requests": 3}


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def measure(conf, spec, seed=2 ** 31 + 11, seconds=2.0, metrics=()):
    """One run of the harness without the look for a chip."""
    return run.measure(conf, spec, list(metrics), seed=seed,
                       seconds=seconds, trace=False,
                       t_start=time.perf_counter(), peaks=PEAKS,
                       clock=run.CompileClock())
