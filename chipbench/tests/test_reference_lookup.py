"""The reference found by the configuration's name decides ``correct``
exactly as the dense reference called directly does: on one recorded
tiny run, the same numbers bit for bit, the program's and each
control's."""
import time

from chipbench import check, run
from chipbench.reference import Reference
from chipbench.tests.helpers import tiny_conf, tiny_spec


def test_lookup_gives_the_dense_references_numbers(monkeypatch):
    conf, spec, seed = tiny_conf("internlm2-1.8b"), tiny_spec(), 2 ** 31 + 41
    assert conf["reference"] == "dense_mcma"
    served = run.serve_cell(conf, spec, seed=seed, seconds=2.0, trace=False,
                            t_start=time.perf_counter(),
                            clock=run.CompileClock())
    controls = ("fp8", "int8")
    by_name = check.check_served(conf, spec, seed, served, controls=controls)
    monkeypatch.setattr(run, "reference_of", lambda _: Reference)
    direct = check.check_served(conf, spec, seed, served, controls=controls)
    assert by_name["numbers"]["checked_tokens_at_least"]["value"] > 0
    assert by_name == direct
    assert set(by_name["controls"]) == set(controls)
