"""PR 26's eight per-layer metrics are data alone: a ``layers/<name>.json``
for one of the readers that were there, and an entry in ``BENCHMARK.json``.
The CPU rehearsal's line carries all eight, finite; and with a snapshot of a
program that lacks the counters and stages (the parent), each reads nothing
and does not raise. About a minute: it boots the real server on the CPU."""

import json
import math
import os
import subprocess
import sys

import run as run_mod

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "inproc.saturate"
NEW = {
    "step_plain_device_ms": "ingest_spans_per_s",
    "step_fused_device_ms": "ingest_spans_per_s",
    "maint_device_share": "ingest_spans_per_s",
    "device_queue_wait_ms": "ack_p95_ms",
    "queue_lanes_ahead": "ack_p95_ms",
    "publish_hold_ms": "ack_p95_ms",
    "publish_drain_ms": "ack_p95_ms",
    "ingest_lock_wait_ms": "ack_p95_ms",
}


def test_entries_and_files_are_data_for_readers_that_were_there():
    bench = run_mod.load_json(ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, moves in NEW.items():
        assert entries[name]["moves"] == moves
        assert entries[name]["workloads"] == [CELL]
        spec = run_mod.load_json(ROOT, "chipbench", "layers", name + ".json")
        assert set(spec) == {"reader", "params"}
        assert spec["reader"] in ("counter_ratio", "stage_delta")
    # appended: the nine that were there still come first, in their order
    assert [m["name"] for m in bench["per_layer"]][9:] == list(NEW)


def test_a_program_without_the_clock_reads_nothing_and_does_not_raise():
    empty = {"stages": {"http_boundary": {"count": 1, "sumUs": 5}},
             "counters": {"spans": 8192, "batches": 1}}
    ctx = {"result": {"before": empty, "after": empty}, "window_s": 30.0}
    for name in NEW:
        assert run_mod.layer_value(name, ctx) is None


def test_the_rehearsal_line_carries_all_eight():
    # 12 s: the publish pair reads nothing in a window that holds no publish
    # of the read mirror, and a 2 s window held one about every second time
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         "2147483801", "--seconds", "12", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 3 and p.stdout.strip() == ""
    marker = "It would have been: "
    line = json.loads([l for l in p.stderr.splitlines()
                       if marker in l][-1].split(marker, 1)[1])
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True, line["compared"]
    values = {name: line["metrics"][name]["value"] for name in NEW}
    assert all(math.isfinite(v) for v in values.values()), values
    assert values["publish_drain_ms"] <= values["publish_hold_ms"]
    assert 0.0 < values["maint_device_share"] < 100.0
