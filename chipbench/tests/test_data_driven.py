"""Every cell, mix, configuration and metric is found by name: a new one
is new files and new entries in ``BENCHMARK.json``, with no edit to a
file that is there.  This test adds one of each in a scratch directory
and runs the harness on them."""
import copy
import json
import time

from chipbench import run, traffic
from chipbench.tests.helpers import PEAKS, tiny_conf, tiny_spec


def test_every_named_file_exists():
    bench = run.load_benchmark()
    for c in bench["configs"]:
        assert (run.ROOT / c["file"]).is_file()
        assert run.load_config(c["name"])["name"] == c["name"]
    for w in bench["workloads"]:
        traffic.load(w["traffic"])
        run.load_config(w["config"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.reader(m["name"])), m["name"]
        for w in m.get("workloads", []):
            run.cell_of(bench, w)


def test_a_new_cell_is_only_new_files(tmp_path, monkeypatch):
    for d in ("configs", "traffic", "metrics"):
        (tmp_path / d).mkdir()
    conf = tiny_conf("mha-layernorm")
    (tmp_path / "configs" / "mha-layernorm.json").write_text(json.dumps(conf))
    (tmp_path / "traffic" / "tiny_burst.json").write_text(
        json.dumps(dict(tiny_spec(), rate_per_s=9.0)))
    (tmp_path / "metrics" / "tokens_generated.py").write_text(
        "def read(ctx):\n    return ctx.served.window.generated\n")
    for name in ("setup_s", "ttft_p95_ms"):
        (tmp_path / "metrics" / f"{name}.py").write_text(
            (run.METRIC_DIR / f"{name}.py").read_text())
    monkeypatch.setattr(run, "CONFIG_DIR", tmp_path / "configs")
    monkeypatch.setattr(run, "METRIC_DIR", tmp_path / "metrics")
    monkeypatch.setattr(traffic, "TRAFFIC_DIR", tmp_path / "traffic")

    bench = copy.deepcopy(run.load_benchmark())
    cell = {"name": "mha-layernorm.tiny_burst", "config": "mha-layernorm",
            "traffic": "tiny_burst", "chips": 1, "why": "test"}
    bench["workloads"].append(cell)
    bench["end_to_end"].append({"name": "tokens_generated",
                                "unit": "tokens", "better": "higher",
                                "bound": 0.1, "source": "host_clock",
                                "workloads": [cell["name"]]})
    next(m for m in bench["end_to_end"] if m["name"] == "ttft_p95_ms")[
        "workloads"].append(cell["name"])

    found = run.cell_of(bench, cell["name"])
    metrics = run.metrics_for(bench, cell["name"], trace=False)
    assert [m["name"] for m in metrics] == ["setup_s", "ttft_p95_ms",
                                            "tokens_generated"]
    res = run.measure(run.load_config(found["config"]),
                      traffic.load(found["traffic"]), metrics, seed=5,
                      seconds=1.5, trace=False,
                      t_start=time.perf_counter(), peaks=PEAKS,
                      clock=run.CompileClock())
    assert res["correct"], res["check"]
    assert set(res["metrics"]) == {"setup_s", "ttft_p95_ms",
                                   "tokens_generated"}
    assert res["metrics"]["tokens_generated"]["value"] > 0


def test_without_a_tpu_no_result_and_a_failing_exit(tmp_path):
    """On this CPU the harness refuses to measure: exit 2, no JSON."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(run.ROOT / "chipbench" / "run.py"),
                        "--workload", "internlm2-1.8b.chat", "--seed",
                        str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=tmp_path)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
