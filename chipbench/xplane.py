"""From a profiler trace to device busy time and device time per program.

    python chipbench/xplane.py <trace dir> <out.json> [--dump]

Run by ``run.py`` as a process of its own (with ``JAX_PLATFORMS=cpu``: it
needs JAX's reader, ``jax.profiler.ProfileData``, and must not touch the
chip). Reads the ``*.xplane.pb`` that ``serve_traced.py`` wrote and
``done.json`` beside it, and writes:

- ``window_s``: the traced slice, from the first to the last event of any
  plane (host threads never rest, so that is the profiler's own window);
- ``busy_s``: per device plane, the union of the intervals in which an XLA
  op ran, averaged over the device planes;
- ``programs``: per jitted program (the ``XLA Modules`` line; ``jit_`` and
  the run id stripped), its runs and device seconds, summed over devices;
- ``device_ops``: the ops that took most device time, ``[name, seconds]``;
- ``idle_gaps``: the idle time of the first device, summed by which programs
  ran before and after the gap (naming what the HOST did in it needs
  annotations inside the program: the ``tracing`` issue's);
- ``slice``: the slice's start and stop on the host's monotonic clock.

``--dump`` prints planes, lines and a few events of each, to look at a trace
by hand before trusting the names above.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)


def program_name(event_name: str) -> str:
    """``jit_spmd_edges_fresh(1234)`` -> ``spmd_edges_fresh``. The device step
    is ``jit_spmd(<hash>)`` in every fused variant (its jitted function is
    named ``spmd``), so all variants reduce to the one name ``spmd``."""
    name = re.sub(r"\(\d+\)$", "", event_name.strip())
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """An XLA op event is named by its whole HLO line: keep the result's
    name and the opcode, ``%while.14 while``."""
    m = re.match(r"^(%[\w.\-]+) = .*?\s([a-z][\w\-]*)\(", event_name)
    return f"{m.group(1)} {m.group(2)}" if m else event_name[:80]


def union_seconds(intervals) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def gaps(intervals, named):
    """Gaps of the union of ``intervals`` as ``(description, seconds)``;
    ``named`` holds ``(start, end, name)`` of the programs, used to say what
    ran before and after each gap (a gap inside one program's run is between
    two of its own ops)."""
    progs = sorted(named)
    starts = [p[0] for p in progs]
    out, cur_hi = [], None
    for lo, hi in sorted(intervals):
        if cur_hi is not None and lo > cur_hi:
            k = bisect.bisect_right(starts, cur_hi) - 1  # started before it
            inside = k >= 0 and progs[k][1] >= lo
            if inside:
                desc = f"inside {progs[k][2]}"
            else:
                before = progs[k][2] if k >= 0 else "?"
                after = progs[k + 1][2] if k + 1 < len(progs) else "?"
                desc = f"after {before}, before {after}"
            out.append((desc, lo - cur_hi))
        cur_hi = hi if cur_hi is None else max(cur_hi, hi)
    return out


def reduce_planes(planes) -> dict:
    """``planes``: [(plane name, [(line name, [(event name, start_s,
    duration_s)])])] -> the reduction described in the module docstring."""
    t_min, t_max = None, None
    busy, programs, ops = [], defaultdict(lambda: [0, 0.0]), defaultdict(float)
    first_gaps = None
    for plane_name, lines in planes:
        for _, events in lines:
            for _, start, dur in events:
                t_min = start if t_min is None else min(t_min, start)
                t_max = start + dur if t_max is None else max(t_max, start + dur)
        if not DEVICE_PLANE.match(plane_name):
            continue
        op_iv, mod_named = [], []
        for line_name, events in lines:
            if line_name in OP_LINES:
                for name, start, dur in events:
                    op_iv.append((start, start + dur))
                    ops[op_name(name)] += dur
            elif line_name in MODULE_LINES:
                for name, start, dur in events:
                    p = programs[program_name(name)]
                    p[0] += 1
                    p[1] += dur
                    mod_named.append((start, start + dur, program_name(name)))
        if not op_iv:  # a device plane with modules only: they are the ops
            op_iv = [(lo, hi) for lo, hi, _ in mod_named]
        busy.append(union_seconds(op_iv))
        if first_gaps is None:
            first_gaps = gaps(op_iv, mod_named)
    window = (t_max - t_min) if t_min is not None else 0.0
    merged = defaultdict(lambda: [0, 0.0])
    for desc, seconds in first_gaps or ():
        merged[desc][0] += 1
        merged[desc][1] += seconds
    longest = sorted(((f"{d} (x{n})", s) for d, (n, s) in merged.items()),
                     key=lambda g: -g[1])
    return {
        "window_s": window,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "device_planes": len(busy),
        "programs": {k: {"count": v[0], "seconds": v[1]}
                     for k, v in programs.items()},
        "device_ops": [[k, v] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[d, s] for d, s in longest[:10]],
    }


def read_planes(path: str):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [
                (ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9)
                for ev in line.events]))
        planes.append((plane.name, lines))
    return planes


def find_trace(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise SystemExit(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def main() -> int:
    trace_dir, out_path = sys.argv[1], sys.argv[2]
    planes = read_planes(find_trace(trace_dir))
    if "--dump" in sys.argv:
        for plane_name, lines in planes:
            print(f"plane {plane_name!r}: {len(lines)} lines")
            for line_name, events in lines:
                names = defaultdict(float)
                for name, _, dur in events:
                    names[name] += dur
                top = sorted(names.items(), key=lambda kv: -kv[1])[:8]
                print(f"  line {line_name!r}: {len(events)} events; "
                      + "; ".join(f"{n[:60]}={s:.4f}s" for n, s in top))
    reduced = reduce_planes(planes)
    done = os.path.join(trace_dir, "done.json")
    if os.path.exists(done):
        with open(done) as f:
            d = json.load(f)
        reduced["slice"] = [d["start"], d["stop"]]
    with open(out_path, "w") as f:
        json.dump(reduced, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
