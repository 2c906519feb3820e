"""A run of the harness, with the look for a chip skipped and the timed
path broken underneath, comes out not correct: once for each fault a
served cell can have."""
import jax
import jax.numpy as jnp
import pytest

from repro.models import approx_ffn
from repro.runtime.server import DecodeServer

from chipbench.tests.helpers import measure, tiny_conf, tiny_spec


def _state_unchanged(monkeypatch):
    """The prefill-chunk step returns the KV pools it was given (its
    positions still advance, so the server itself sees nothing amiss)."""
    real = DecodeServer._prefill

    def prefill(self, params, cache, *rest, **kw):
        new, m = real(self, params, jax.tree.map(jnp.copy, cache), *rest,
                      **kw)
        return dict(new, k=cache["k"], v=cache["v"]), m
    monkeypatch.setattr(DecodeServer, "_prefill", prefill)


def _token_altered(monkeypatch):
    """The decode step's logits move one id along, so every sampled
    token is the next id after the right one."""
    real = DecodeServer._decode

    def decode(self, *args, **kw):
        logits, cache, m = real(self, *args, **kw)
        return jnp.roll(logits, 1, axis=-1), cache, m
    monkeypatch.setattr(DecodeServer, "_decode", decode)


def _half_left_out(monkeypatch):
    """Every tick runs half of the rows its capacities admit; the rest
    get no FFN."""
    real = approx_ffn.serve_caps

    def caps(cfg, t):
        ec, ic = real(cfg, t)
        return max(ec // 2, 1), max(ic // 2, 1)
    monkeypatch.setattr(approx_ffn, "serve_caps", caps)


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered,
                                   _half_left_out])
def test_fault_is_not_correct(fault, monkeypatch):
    conf, spec = tiny_conf("internlm2-1.8b"), tiny_spec()
    fault(monkeypatch)
    res = measure(conf, spec)
    assert not res["correct"], res["check"]
