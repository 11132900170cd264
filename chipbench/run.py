#!/usr/bin/env python3
"""chipbench: one cell, once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Boots the server as a child the documented way with the environment the
cell's configuration file lists, lets ``client.py`` (a process of its own)
warm the device step, fill the ring and offer the cell's traffic for
``--seconds``, waits until every acknowledged span is applied, compares the
server's answers with the plain reference (``reference.py``, ``compare.py``) and prints, last,
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, and with ``--trace 1`` ``breakdown``; then ``compared``, each
number beside its limit. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics. A run that fails prints no result, exits
with code 1, and ends its standard error with one JSON object,
``{"failed_in": <phase>, "reason": ...}``, the phase one of ``PHASES``.

This process and the client never import JAX: the chip belongs to the server
child. A run whose server is not on a TPU, or sees another number of chips
than the cell asks for, prints no result and exits with code 3
(``--rehearse``, for the CPU: it runs through, prints what it would have said
to standard error only, and still exits 3).

Everything that belongs to one cell, one configuration or one per-layer
metric is a file found by the name ``BENCHMARK.json`` gives: see README.md.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback

T_START = time.monotonic()  # set-up counts from here

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import compare as compare_mod  # noqa: E402
import gen  # noqa: E402
import measures  # noqa: E402
import reference  # noqa: E402
from launcher import TRANSPORT_ERRORS, RunFailure, Server  # noqa: E402

RUN_LIMIT_S = 1150  # a cold first run compiles; the driver allows 1200
# where a run can fail, in order; the client's lines move the middle ones on
PHASES = ("boot", "setup", "window", "drain", "settle", "fetch", "trace",
          "compare")
CLIENT_SAYS = {"WINDOW": "window", "CLOSED": "drain", "DRAINED": "settle"}
TRACE_SLICE_S = 4.0
FINAL_NAME_SERVICES = 5  # services whose span names are compared


def load_json(*parts: str):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """-> (benchmark, cell entry, configuration file, workload file)."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, cfg_entry["file"])
    workload = load_json(HERE, "workloads", name + ".json")
    if workload["config"] != cell["config"]:
        raise SystemExit(f"{name}: workload file names another configuration")
    return bench, cell, config, workload


def finite(value) -> bool:
    return isinstance(value, (int, float)) and math.isfinite(value)


def metrics_of(bench: dict, cell: str, group: str):
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def fetch_final(server: Server, config: dict, seed: int) -> dict:
    """The server's answers after the drain, fresh (``staleness_ms=0``) and
    under keys that set-up did not ask for: a cold key is computed, not
    served from a cache that the overload controller may still be holding
    reads to."""
    http = server.http
    window = gen.query_window(900_000)
    c = config["applied_counter"]
    final = {
        "applied": int(http.get_json(c["path"]).get(c["key"], 0)),
        "dependencies": http.get_json(
            "/api/v2/dependencies", staleness_ms=0, **window),
        "percentiles": http.get_json(
            "/api/v2/tpu/percentiles", q="0.5,0.99,0.999", staleness_ms=0),
        "cardinalities": http.get_json(
            "/api/v2/tpu/cardinalities", staleness_ms=0),
        "services": http.get_json("/api/v2/services"),
        "span_names": {},
    }
    rng = random.Random(seed ^ 0xF1A1)
    n_svc = config["fleet"]["services"]
    for i in rng.sample(range(n_svc), min(FINAL_NAME_SERVICES, n_svc)):
        svc = f"svc{i:02d}"
        final["span_names"][svc] = http.get_json(
            "/api/v2/spans", serviceName=svc)
    return final


def settle(server: Server, config: dict, result: dict) -> float:
    """An answer may be as stale as the configuration says, and of any age
    while the overload controller serves reads from its cache alone: wait
    until it no longer reads ``cache_only`` (a minute at most: late is late,
    not wrong), then the stated bound past the drain. -> seconds waited."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < 60.0:
        mode = server.http.get_json("/api/v2/tpu/statusz").get(
            "overload", {}).get("readMode", "normal")
        if mode != "cache_only":
            break
        time.sleep(0.5)
    calm = time.monotonic()
    rest = max(calm, result["t_drained"]) \
        + config["guarantees"]["settle_ms"] / 1000.0 - time.monotonic()
    if rest > 0:
        time.sleep(rest)
    return time.monotonic() - t0


def read_device(server: Server) -> dict:
    dev = server.http.get_json("/api/v2/tpu/statusz").get("device", {})
    per = dev.get("hbm", {}).get("perDevice", [])
    return {
        "platform": dev.get("platform"), "kind": dev.get("deviceKind"),
        "count": dev.get("count"),
        "memory_peak_bytes": max(
            (d.get("peakBytesInUse", 0) for d in per), default=0),
        "programs": dev.get("programs", {}),
    }


def layer_value(name: str, ctx: dict):
    """One per-layer metric through its reader, or None where the reader
    finds nothing to read. The reader and its parameters are in
    ``layers/<name>.json``; a name with a suffix after a dot (a quantity split
    by the end-to-end metric it moves) falls back to the file of its stem."""
    path = os.path.join(HERE, "layers", name + ".json")
    if not os.path.exists(path):
        path = os.path.join(HERE, "layers", name.split(".")[0] + ".json")
    spec = load_json(path)
    path = os.path.join(HERE, "readers", spec["reader"] + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_reader_" + spec["reader"], path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx, spec.get("params", {}))


def setup_waits(at_health: dict, result: dict) -> dict:
    """What the set-up waited for, for ``notes``: the programs the server
    compiled between ``/health`` and the window (or read from the compile
    cache: a hit counts as a compile of some tenths of a second) and the
    walls of those compiles summed, by the server's own table; and the longest
    POST of the set-up. A cold run shows as one in its own line."""
    before = result["before"]
    compiles = sum(c[1] for c in before["program_calls"].values()) - sum(
        p.get("compiles", 0) for p in at_health.values())
    wall_ms = sum(before["program_compile_ms"].values()) - sum(
        p.get("compileWallMs", 0.0) for p in at_health.values())
    posts = [s["acked"] - s["due"] for s in result["sends"]
             if s["phase"] == "fill" and s["acked"] is not None]
    return {"setup_compiles": compiles, "setup_compile_s": wall_ms / 1000.0,
            "setup_post_max_s": max(posts, default=None)}


def reduce_trace(trace_dir: str) -> dict:
    """The xplane reducer in a process of its own, after the server is gone:
    reading a trace needs JAX's reader, and this process stays off JAX."""
    out = os.path.join(trace_dir, "reduced.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "xplane.py"), trace_dir, out],
        env=env, capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        raise RunFailure(f"xplane.py failed: {proc.stderr[-2000:]}")
    return load_json(out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run off a TPU; the result goes to stderr, exit 3")
    ap.add_argument("--control", action="store_true",
                    help="also put the reference in the program's place, "
                    "sound and with a guarantee broken (control.py)")
    ap.add_argument("--late", type=float, default=0.0,
                    help="the client waits this long after /health before "
                    "its first POST: moves the set-up against the server's "
                    "tickers (it counts in setup_s)")
    ap.add_argument("--record",
                    help="keep the client's record (every send with its "
                    "times) in this file, for measures.py's sub-windows")
    args = ap.parse_args()
    bench, cell, config, workload = load_cell(args.workload)
    traced = bool(args.trace)

    def on_signal(signum, _frame):
        raise RunFailure(f"stopped by signal {signum}")

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGALRM, on_signal)
    signal.alarm(RUN_LIMIT_S)

    workdir = tempfile.mkdtemp(prefix="chipbench_")
    slice_s = min(TRACE_SLICE_S, args.seconds / 2)
    server = Server(config, workdir, traced, trace_seconds=slice_s)
    client = None
    timer = None
    phase = "boot"
    try:
        # the client starts at once and builds its templates while the
        # server boots; so does this process, for the reference
        spec = {"port": server.port, "seed": args.seed,
                "seconds": args.seconds, "config": config,
                "workload": workload, "late_s": args.late,
                "out": os.path.join(workdir, "client.json")}
        with open(os.path.join(workdir, "spec.json"), "w") as f:
            json.dump(spec, f)
        client = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "client.py"),
             os.path.join(workdir, "spec.json")],
            stdout=subprocess.PIPE, text=True, start_new_session=True)
        traffic = gen.Traffic(args.seed, config["fleet"], workload["posts"])
        server.wait_health()
        device = read_device(server)
        programs_at_health = device["programs"]
        on_chip = (device["platform"] == "tpu"
                   and device["count"] == cell["chips"])
        if not on_chip and not args.rehearse:
            print(f"chipbench: the server's device is {device['platform']!r} "
                  f"x{device['count']}, the cell needs tpu x{cell['chips']}",
                  file=sys.stderr)
            return 3
        setup_s = None
        phase = "setup"
        client_failure = None
        for line in client.stdout:
            word = line.split(" ", 1)[0]
            phase = CLIENT_SAYS.get(word, phase)
            if word == "FAILED":
                client_failure = json.loads(line.split(" ", 1)[1])
                phase = client_failure["failed_in"]
            if word == "WINDOW":
                t0 = float(line.split()[1])
                setup_s = t0 - T_START
                if traced:
                    timer = threading.Timer(
                        max(0.0, t0 + (args.seconds - slice_s) / 2
                            - time.monotonic()),
                        server.signal, (signal.SIGUSR1,))
                    timer.start()
            if not server.alive():
                raise RunFailure("the server died during the run")
        if client.wait() != 0 or setup_s is None:
            raise RunFailure(
                f"the client exited with {client.returncode}: "
                + (client_failure or {}).get("reason", "it did not say why"))
        result = load_json(spec["out"])
        if args.record:
            shutil.copyfile(spec["out"], args.record)

        device = read_device(server)  # the peak, before anything else runs
        settle_s = settle(server, config, result)
        phase = "fetch"
        final = fetch_final(server, config, args.seed)
        xplane = None
        phase = "trace"
        if traced:
            done = os.path.join(workdir, "trace", "done.json")
            t_wait = time.monotonic()
            while not os.path.exists(done):
                if time.monotonic() - t_wait > 120 or not server.alive():
                    raise RunFailure("the profiler did not write its trace")
                time.sleep(0.1)
        server.stop()
        if traced:
            xplane = reduce_trace(os.path.join(workdir, "trace"))

        # the reference, once the window has closed and the server is gone
        phase = "compare"
        ref = reference.Reference(traffic.templates)
        detail = {}
        numbers = compare_mod.compare(
            ref, traffic, result, final, config["guarantees"], detail)
        e2e, ops = measures.end_to_end(result, setup_s)
        correct = compare_mod.verdict(numbers)
        if traced:
            peaks = load_json(HERE, "peaks.json")
            if on_chip and device["kind"] not in peaks:
                raise RunFailure(
                    f"no peaks for device kind {device['kind']!r} in peaks.json")
            ctx = {"result": result, "config": config, "workload": workload,
                   "xplane": xplane if on_chip else None,
                   "device": device, "on_chip": on_chip,
                   "peaks": peaks.get(device["kind"]),
                   "window_s": ops["window_s"] + ops["drain_s"]}
            wanted = metrics_of(bench, cell["name"], "per_layer")
            values = {m["name"]: layer_value(m["name"], ctx) for m in wanted}
        else:
            wanted = metrics_of(bench, cell["name"], "end_to_end")
            values = {m["name"]: e2e.get(m["name"]) for m in wanted}
        line = {
            "correct": correct, "attempted": ops["attempted"],
            "failed": ops["failed"],
            "metrics": {m["name"]: {"value": values[m["name"]],
                                    "unit": m["unit"]}
                        for m in wanted if finite(values.get(m["name"]))},
            "device": {k: device[k] for k in
                       ("platform", "kind", "count", "memory_peak_bytes")},
        }
        if traced and xplane is not None and on_chip:
            line["device"]["busy_s"] = xplane["busy_s"]
            line["device"]["window_s"] = xplane["window_s"]
            line["breakdown"] = {"device_ops": xplane["device_ops"][:10],
                                 "idle_gaps": xplane["idle_gaps"][:10]}
        line["notes"] = dict(ops, e2e=e2e, settle_s=settle_s,
                             compare_detail=detail,
                             **measures.setup_parts(result, T_START),
                             **setup_waits(programs_at_health, result))
        if args.control:
            import control
            line["notes"]["control"] = control.readings(
                ref, traffic, result, config["guarantees"])
        line["compared"] = numbers
        for name, (value, limit) in numbers.items():
            flag = "" if value <= limit else "   <-- over its limit"
            print(f"compared {name}: {value!r} limit {limit!r}{flag}",
                  file=sys.stderr)
        sys.stderr.flush()
        if not on_chip:
            print("chipbench: rehearsal off the chip, no result. It would "
                  "have been: " + json.dumps(line), file=sys.stderr)
            return 3
        print(json.dumps(line), flush=True)
        return 0
    except Exception as e:  # the run's boundary: said last, on one line
        if not isinstance(e, (RunFailure, subprocess.TimeoutExpired,
                              *TRANSPORT_ERRORS)):
            traceback.print_exc()
        print(f"chipbench: {type(e).__name__}: {e}", file=sys.stderr)
        print("---- server stderr (tail) ----", file=sys.stderr)
        print(server.stderr_tail(), file=sys.stderr)
        print(json.dumps({"failed_in": phase,
                          "reason": f"{type(e).__name__}: {e}"}),
              file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
        if timer is not None:
            timer.cancel()
        if client is not None and client.poll() is None:
            try:
                os.killpg(client.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            client.wait()
        server.stop()
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
