"""``run.py`` end to end on the CPU: it refuses to report, says
``platform: cpu``, and, with the timed path broken underneath, sees
``correct`` come out false. These boot the real server on the CPU backend at
the default sizes: about a minute each."""

import json
import os
import subprocess
import sys

import gen
import run as run_mod

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "inproc.saturate"
ARGS = ["--workload", CELL, "--seed", "2147483777", "--seconds", "2"]


def run(*extra, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *ARGS, *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)


def would_have_been(stderr: str) -> dict:
    marker = "It would have been: "
    line = [l for l in stderr.splitlines() if marker in l][-1]
    return json.loads(line.split(marker, 1)[1])


def test_off_the_chip_no_result_and_a_code_other_than_0():
    p = run("--trace", "0")
    assert p.returncode == 3
    assert p.stdout.strip() == ""
    assert "'cpu'" in p.stderr


def test_rehearsal_runs_through_says_cpu_and_reports_no_device_metric():
    p = run("--trace", "1", "--rehearse")
    assert p.returncode == 3 and p.stdout.strip() == ""
    line = would_have_been(p.stderr)
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True, line["compared"]
    assert "busy_s" not in line["device"] and "breakdown" not in line
    for name in ("step_device_ms", "step_roofline", "device_idle_share",
                 "hbm_peak_gb"):
        assert name not in line["metrics"]
    assert line["metrics"]["lanes_per_step"]["value"] == 8192
    assert list(line)[-1] == "compared"


def test_the_timed_path_broken_underneath_is_not_correct(monkeypatch, capfd):
    """The rest of a run, driven past the look for a chip, with the server
    made to apply a batch that the reference never saw: every answer is
    altered where it is produced."""
    bench, cell, config, workload = run_mod.load_cell(CELL)
    extra = gen.Traffic(2147483777, config["fleet"], workload["posts"]).body(10 ** 6)
    settle = run_mod.settle

    def broken(server, config, result):
        status, _, _ = server.http.request(
            "POST", "/api/v2/spans", extra, {"Content-Type": "application/json"})
        assert status == 202
        return settle(server, config, result)

    monkeypatch.setattr(run_mod, "settle", broken)
    monkeypatch.setattr(sys, "argv", ["run.py", *ARGS, "--trace", "0", "--rehearse"])
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert run_mod.main() == 3
    err = capfd.readouterr().err
    line = would_have_been(err)
    assert line["correct"] is False
    assert line["compared"]["spans_applied_diff"] == [8192, 0]
    assert line["compared"]["links_wrong_edges"][0] > 0
    # each number beside its limit, as the last lines of standard error
    assert "compared links_wrong_edges:" in err
