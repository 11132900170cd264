"""Device-program observatory: runtime visibility into jitted programs.

The static lint (ZT03) proves no *avoidable* recompile triggers exist in
the source; this is the dynamic complement. Every jitted/shard_map
entrypoint (ingest step variants, rollup, the spmd_* read programs) is
wrapped at build time in :func:`DeviceObservatory.wrap`, which captures:

- **call count + enqueue wall** (``callWallMs`` / ``maxCallMs``): the
  host time of the asynchronous dispatch. It ends when the program is in
  the device's queue, so it is HOST time: a millisecond for a step that
  runs 2 s, or seconds for a dispatch that the runtime holds back because
  too many programs are in flight. The stage ``device_dispatch`` is the
  same wall at its call site;
- **device time and queue wait** (``done`` / ``deviceMs`` /
  ``queueWaitMs`` / ``maxDeviceMs``) from the **completion clock**
  (:class:`DeviceQueue`): every observed call leaves a completion token,
  one daemon thread waits on the tokens in dispatch order and stamps when
  each program had RUN. A read program's token is the smallest leaf of
  its own output. A state-returning program (``token="state"``: the step
  variants, ``spmd_flush``, ``spmd_rollup``, ``spmd_init``) donates its
  output to the next call, so nothing of it can be held: right behind the
  call, still under the caller's lock, the marker copies the smallest
  leaf of the new state (one word, the first device's shard) into pinned
  host memory, and that copy is the token. The marker is a transfer and
  not a jitted program, because a program takes a slot in the runtime's
  window of 32 programs in flight (PERF.md section 6, PR 26). The clock
  follows this observatory's switch: off means no marker, no token, no
  thread;
- **compile count + compile wall** via the jit cache-size delta: jax's
  ``jitted._cache_size()`` grows once per distinct input-shape
  signature, so ``after > before`` around a call means that call paid a
  trace+compile — a *runtime recompile detector*. Steady state must
  show zero growth after warmup;
- **``cost_analysis()`` / ``memory_analysis()`` at first compile**,
  captured through an AOT ``lower().compile()`` of the same arguments.
  JAX serves that second compile from its in-process executable cache
  (``analysisWallMs``: a millisecond or two per program on a v5e, where
  the first compile took up to two minutes); a failure is counted
  (``analysisFailures``), not hidden. Disable with
  ``TPU_OBS_DEVICE_ANALYSIS=0``. The AOT path does not populate the jit
  dispatch cache, so it never perturbs the recompile detector;
- **live-HBM and host-transfer gauges**: accelerator
  ``memory_stats()`` (absent on CPU) and the readpack transfer
  count/bytes, surfaced next to the existing ``hostTransfers`` counter.

Counter updates are plain attribute writes: device dispatches are
serialized under the aggregator lock, and these are debug gauges — a
rare torn increment from an exotic caller skews a count, nothing more.
The registry is process-global and name-keyed; ``_compiled_programs``
is lru-cached per (config, mesh), so one name may accumulate several
entries over a test run — reads merge them.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence

from zipkin_tpu.obs import querytrace

logger = logging.getLogger(__name__)

_QUEUE_CAPACITY = 4096  # entries the clock holds; beyond: dropped, counted
_QUEUE_RING = 256       # finished entries kept for statusz


class Fence:
    """An entry of the device queue without a token: it resolves when
    the entry before it has run. ``wait`` gives the seconds from its
    entry to that moment: what a reader that entered the queue here
    waited for programs queued before it."""

    __slots__ = ("entered_ns", "resolved_ns", "_event")

    def __init__(self, entered_ns: int) -> None:
        self.entered_ns = entered_ns
        self.resolved_ns = 0
        self._event = threading.Event()

    def resolve(self, at_ns: int) -> None:
        self.resolved_ns = at_ns
        self._event.set()

    def wait(self, timeout: Optional[float] = None) -> Optional[float]:
        if not self._event.wait(timeout):
            return None
        return (self.resolved_ns - self.entered_ns) / 1e9


class DeviceQueue:
    """The completion clock: when each dispatched program had run, and
    from that its device time and its wait in the device's queue.

    ``push`` (the dispatching thread, under the caller's lock) appends
    one entry; one daemon thread, started at the first push, takes the
    entries in dispatch order, blocks on the token (the GIL is released
    there) and stamps ``t_done``. One chip runs programs in the order
    they were launched, so with ``start = max(t_enq, t_done of the entry
    before)``: device time is ``t_done - start``, queue wait ``start -
    t_enq``. The sums telescope: a late wake-up of the thread moves time
    between neighbours and invents none. ``t_enq`` is stamped when the
    dispatch RETURNED, so a compile, or a dispatch the runtime held
    back, is in the enqueue wall and not here. All stamps are
    ``perf_counter_ns``, the clock of ``obs.record`` and ``querytrace``.

    An entry stays in the queue until it has run: the depth gauges count
    what the host has handed over and the device has not done. Bounded:
    at ``capacity`` entries a new token is dropped and counted, and the
    entry after the hole only anchors the chain, so no device time spans
    a hole. A token that raises is a hole too.
    """

    def __init__(self, capacity: int = _QUEUE_CAPACITY,
                 ring: int = _QUEUE_RING,
                 now: Callable[[], int] = time.perf_counter_ns) -> None:
        self._capacity = int(capacity)
        self._now = now
        self._cond = threading.Condition()
        self._fifo: deque = deque()
        self._recent: deque = deque(maxlen=int(ring))
        self._thread: Optional[threading.Thread] = None
        self._hole = False  # the next entry pushed follows a dropped one
        # step counters (the benchmark's per-layer metrics read these)
        self.step_done = {"plain": 0, "fused": 0}
        self.step_device_ns = {"plain": 0, "fused": 0}
        self.step_queue_wait_ns = 0
        self.step_lanes_ahead_sum = 0
        # depth: steps handed to the device and not run yet
        self.steps = 0
        self.lanes = 0
        self.lanes_max = 0
        self.depth_max = 0
        self.dropped = 0

    # -- dispatching threads ----------------------------------------------

    def push(self, stats: "ProgramStats", token: Any, t_enq_ns: int,
             lanes: int = 0, seq: int = 0,
             on_done: Optional[Callable[[float], None]] = None) -> bool:
        """One dispatched program: ``token.block_until_ready()`` returns
        when it has run; ``on_done(device seconds)`` is then called from
        the clock's thread. False when the queue is full."""
        step = stats.step is not None
        with self._cond:
            if len(self._fifo) >= self._capacity:
                self.dropped += 1
                self._hole = True
                return False
            anchor, self._hole = self._hole, False
            if step:
                self.step_lanes_ahead_sum += self.lanes
                self.steps += 1
                self.lanes += lanes
                if self.lanes > self.lanes_max:
                    self.lanes_max = self.lanes
            self._enter((stats, int(lanes), int(seq), t_enq_ns, token,
                         on_done, anchor))
        return True

    def fence(self) -> Optional[Fence]:
        """Enter a :class:`Fence` behind what is queued now (None when
        the queue is full)."""
        fence = Fence(self._now())
        with self._cond:
            if not self._fifo:  # nothing ahead: resolved as it enters
                fence.resolve(fence.entered_ns)
                return fence
            if len(self._fifo) >= self._capacity:
                self.dropped += 1
                return None
            self._enter((None, 0, 0, fence.entered_ns, fence, None, False))
        return fence

    def _enter(self, entry: tuple) -> None:  # zt-lint: disable=ZT04 — both callers hold self._cond
        self._fifo.append(entry)
        if len(self._fifo) > self.depth_max:
            self.depth_max = len(self._fifo)
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="device-clock", daemon=True)
            self._thread.start()
        self._cond.notify_all()

    # -- the clock's thread -----------------------------------------------

    def _run(self) -> None:
        prev_done = 0  # the device was idle before the first entry
        while True:
            with self._cond:
                while not self._fifo:
                    self._cond.wait()
                entry = self._fifo[0]  # it counts as queued until it has run
            stats, lanes, seq, t_enq, token, on_done, anchor = entry
            if stats is None:  # a fence: nothing of its own to wait for
                token.resolve(max(prev_done, t_enq))
                with self._cond:
                    self._fifo.popleft()
                    self._cond.notify_all()
                continue
            failed = False
            try:
                # zt-lint: disable=ZT06 — the clock's whole point: this
                # thread alone waits for the device, off every serving
                # path and never under the aggregator lock
                token.block_until_ready()
            except Exception:
                logger.warning("device clock: the token of %s failed",
                               stats.name, exc_info=True)
                failed = True
            t_done = self._now()
            del token, entry
            start = max(t_enq, prev_done)
            prev_done = t_done
            device_ns, wait_ns = t_done - start, start - t_enq
            booked = not (failed or anchor)
            if booked and on_done is not None:
                try:
                    on_done(device_ns / 1e9)
                except Exception:
                    logger.exception("device clock: on_done of %s failed",
                                     stats.name)
            with self._cond:
                self._fifo.popleft()
                if stats.step is not None:
                    self.steps -= 1
                    self.lanes -= lanes
                if failed:
                    self._hole = True
                if not booked:
                    self.dropped += 1
                else:
                    stats.done += 1
                    stats.device_ns += device_ns
                    stats.queue_wait_ns += wait_ns
                    if device_ns > stats.max_device_ns:
                        stats.max_device_ns = device_ns
                    if stats.step is not None:
                        self.step_done[stats.step] += 1
                        self.step_device_ns[stats.step] += device_ns
                        self.step_queue_wait_ns += wait_ns
                    self._recent.append(
                        (seq, stats.name, lanes, t_enq, start, t_done))
                self._cond.notify_all()

    # -- query side -----------------------------------------------------------

    @property
    def thread(self) -> Optional[threading.Thread]:
        return self._thread

    def wait_idle(self, timeout: Optional[float] = None) -> bool:
        """Block until every entry pushed so far is booked (tests, and
        ``ShardedAggregator.block_until_ready``)."""
        with self._cond:
            return self._cond.wait_for(lambda: not self._fifo, timeout)

    def counters(self) -> Dict[str, int]:
        """Flat, for ``/api/v2/tpu/counters``: present from boot at 0."""
        with self._cond:
            return {
                "stepPlainDone": self.step_done["plain"],
                "stepPlainDeviceUs": self.step_device_ns["plain"] // 1000,
                "stepFusedDone": self.step_done["fused"],
                "stepFusedDeviceUs": self.step_device_ns["fused"] // 1000,
                "stepQueueWaitUs": self.step_queue_wait_ns // 1000,
                "stepLanesAheadSum": self.step_lanes_ahead_sum,
                "deviceQueueSteps": self.steps,
                "deviceQueueLanes": self.lanes,
                "deviceQueueLanesMax": self.lanes_max,
                "deviceQueueDropped": self.dropped,
            }

    def status(self) -> Dict:
        """The ``statusz.device.queue`` block."""
        with self._cond:
            recent = list(self._recent)
            body = {
                "depth": len(self._fifo),
                "depthMax": self.depth_max,
                "steps": self.steps,
                "lanes": self.lanes,
                "lanesMax": self.lanes_max,
                "dropped": self.dropped,
                "capacity": self._capacity,
            }
        body["recent"] = [
            {"seq": seq, "program": name, "lanes": lanes, "enqNs": t_enq,
             "startNs": start, "doneNs": t_done}
            for seq, name, lanes, t_enq, start, t_done in recent
        ]
        return body

    def reset_counters(self) -> None:
        with self._cond:
            self.step_done = {"plain": 0, "fused": 0}
            self.step_device_ns = {"plain": 0, "fused": 0}
            self.step_queue_wait_ns = 0
            self.step_lanes_ahead_sum = 0
            self.lanes_max = self.lanes
            self.depth_max = len(self._fifo)
            self.dropped = 0
            self._recent.clear()


@functools.lru_cache(maxsize=8)
def _host_memory(device: Any) -> Any:
    """Pinned host memory beside ``device``, as a sharding; None where
    the backend has no such memory kind."""
    from jax.sharding import SingleDeviceSharding

    if all(m.kind != "pinned_host" for m in device.addressable_memories()):
        return None
    return SingleDeviceSharding(device, memory_kind="pinned_host")


def _state_token(stats: "ProgramStats", state: Any) -> Any:
    """The marker behind a state-returning program: a copy of the
    smallest leaf of the new state (the first device's shard, 4 bytes in
    this program) into pinned host memory. A transfer, not a program: a
    program would take one of the runtime's 32 slots for programs in
    flight and halve how far the host may run ahead (measured: PERF.md
    section 6, PR 26). The leaf is donated with the rest of the state to
    the next call, which the runtime orders after this read as after any
    read program; the copy is a buffer of its own, and the clock never
    holds the leaf."""
    import jax

    leaves = jax.tree_util.tree_leaves(state)
    if stats._token_leaf is None:
        stats._token_leaf = min(
            range(len(leaves)), key=lambda i: leaves[i].size)
    shard = leaves[stats._token_leaf].addressable_data(0)
    host = _host_memory(shard.device)
    return None if host is None else jax.device_put(shard, host)


def _output_token(out: Any) -> Any:
    """The smallest array of a read program's output (never donated)."""
    import jax

    leaves = [a for a in jax.tree_util.tree_leaves(out)
              if hasattr(a, "block_until_ready")]
    return min(leaves, key=lambda a: a.size) if leaves else None


_tag = threading.local()


def tag_next(lanes: int = 0, seq: int = 0,
             on_done: Optional[Callable[[float], None]] = None) -> None:
    """What the calling thread's NEXT observed dispatch carries into the
    device queue's record: its lanes, the caller's sequence number, and a
    callback for its device time (called from the clock's thread)."""
    if OBSERVATORY.enabled:
        _tag.next = (lanes, seq, on_done)


class ProgramStats:
    """Counters for one wrapped program build (one jit'd callable)."""

    __slots__ = ("name", "token", "step", "calls", "compiles",
                 "call_wall_s", "done", "device_ns", "queue_wait_ns",
                 "max_device_ns", "compile_wall_s", "last_compile_s",
                 "max_call_s", "cache_size", "cost", "memory",
                 "analysis_wall_s", "analysis_error", "_analysis_tried",
                 "_cache_size_fn", "_token_leaf")

    def __init__(self, name: str, fn: Callable, token: str = "output",
                 step: Optional[str] = None) -> None:
        self.name = name
        # what the completion clock waits on: the program's own
        # "output", or a marker behind a "state" that is donated on
        self.token = token
        # "plain" / "fused" for the ingest step variants, else None
        self.step = step
        self._token_leaf: Optional[int] = None
        self.calls = 0
        self.compiles = 0
        self.call_wall_s = 0.0  # enqueue walls (async dispatch)
        # the clock's: runs seen done, their device time and queue wait
        self.done = 0
        self.device_ns = 0
        self.queue_wait_ns = 0
        self.max_device_ns = 0
        self.compile_wall_s = 0.0
        self.last_compile_s = 0.0
        self.max_call_s = 0.0
        self.cache_size = 0
        self.cost: Optional[Dict[str, float]] = None
        self.memory: Optional[Dict[str, int]] = None
        self.analysis_wall_s = 0.0
        self.analysis_error: Optional[str] = None
        self._analysis_tried = False
        # private jax API, probed once; absent -> no recompile detection
        self._cache_size_fn = getattr(fn, "_cache_size", None)

    @property
    def recompiles(self) -> int:
        """Compiles beyond the first: shape churn after warmup."""
        return max(0, self.compiles - 1)

    def observe(self, fn: Callable, args: tuple, kw: dict,
                analysis: bool, queue: DeviceQueue) -> Any:
        tag = getattr(_tag, "next", None)
        _tag.next = None
        size_fn = self._cache_size_fn
        before = size_fn() if size_fn is not None else -1
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        dt = time.perf_counter() - t0
        t_enq = time.perf_counter_ns()
        token = (_state_token(self, out) if self.token == "state"
                 else _output_token(out))
        if token is not None:
            queue.push(self, token, t_enq, *(tag or ()))
        # query-plane observatory: when the calling thread carries an
        # armed QueryTrace (read path only), the enqueue wall of this
        # program is that query's device_dispatch segment. perf_counter
        # and perf_counter_ns share a clock, so the ns conversion is
        # exact enough for the stitcher's gap sweep.
        querytrace.stamp_active(
            querytrace.QSEG_DEVICE_DISPATCH,
            int(t0 * 1e9), int((t0 + dt) * 1e9),
        )
        self.calls += 1
        self.call_wall_s += dt
        if dt > self.max_call_s:
            self.max_call_s = dt
        if size_fn is not None:
            after = size_fn()
            if after > before:
                self.compiles += after - before
                self.compile_wall_s += dt
                self.last_compile_s = dt
                self.cache_size = after
                if analysis and not self._analysis_tried:
                    self._capture_analysis(fn, args, kw)
        return out

    def _capture_analysis(self, fn: Callable, args: tuple,
                          kw: dict) -> None:
        self._analysis_tried = True
        try:
            t0 = time.perf_counter()
            compiled = fn.lower(*args, **kw).compile()
            self.analysis_wall_s = time.perf_counter() - t0
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if isinstance(ca, dict):
                self.cost = {
                    "flops": float(ca.get("flops", 0.0)),
                    "bytesAccessed": float(ca.get("bytes accessed", 0.0)),
                }
            ma = compiled.memory_analysis()
            if ma is not None:
                self.memory = {
                    "generatedCodeBytes": int(getattr(
                        ma, "generated_code_size_in_bytes", 0)),
                    "argumentBytes": int(getattr(
                        ma, "argument_size_in_bytes", 0)),
                    "outputBytes": int(getattr(
                        ma, "output_size_in_bytes", 0)),
                    "tempBytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                }
        except Exception as e:
            # a debug gauge must not fail the dispatch it rides on, but
            # the failure is counted where statusz shows it
            self.analysis_error = f"{type(e).__name__}: {e}"[:200]
            logger.warning("cost analysis of %s failed", self.name,
                           exc_info=True)

    def as_dict(self) -> Dict:
        d: Dict = {
            "calls": self.calls,
            "compiles": self.compiles,
            "recompiles": self.recompiles,
            # enqueue wall: host time of the asynchronous dispatch
            "callWallMs": round(self.call_wall_s * 1e3, 3),
            "compileWallMs": round(self.compile_wall_s * 1e3, 3),
            "lastCompileMs": round(self.last_compile_s * 1e3, 3),
            "maxCallMs": round(self.max_call_s * 1e3, 3),
            # the completion clock's: time ON the device
            "done": self.done,
            "deviceMs": round(self.device_ns / 1e6, 3),
            "queueWaitMs": round(self.queue_wait_ns / 1e6, 3),
            "maxDeviceMs": round(self.max_device_ns / 1e6, 3),
        }
        if self._analysis_tried:
            d["analysisWallMs"] = round(self.analysis_wall_s * 1e3, 3)
        if self.cost is not None:
            d["cost"] = self.cost
        if self.memory is not None:
            d["memory"] = self.memory
        if self.analysis_error is not None:
            d["analysisError"] = self.analysis_error
        return d


class DeviceObservatory:
    """Process-global registry of wrapped device programs."""

    def __init__(self, enabled: bool = True, analysis: bool = True) -> None:
        self._enabled = bool(enabled)
        self._analysis = bool(analysis)
        self._lock = threading.Lock()
        self._programs: Dict[str, List[ProgramStats]] = {}
        self.queue = DeviceQueue()

    def wrap(self, name: str, fn: Callable, token: str = "output",
             step: Optional[str] = None) -> Callable:
        """Wrap one jitted callable; transparent when disabled.
        ``token`` and ``step`` are :class:`ProgramStats`'s."""
        entry = ProgramStats(name, fn, token, step)
        with self._lock:
            self._programs.setdefault(name, []).append(entry)
        obs = self

        def wrapper(*args, **kw):
            if not obs._enabled:
                return fn(*args, **kw)
            return entry.observe(fn, args, kw, obs._analysis, obs.queue)

        wrapper.__name__ = name
        wrapper.__wrapped__ = fn
        wrapper.program_stats = entry
        # AOT path stays reachable (test_chip_compile lower()s programs)
        lower = getattr(fn, "lower", None)
        if lower is not None:
            wrapper.lower = lower
        return wrapper

    def fence(self) -> Optional[Fence]:
        """A fence in the device queue behind what is queued now; None
        with the observatory off (or the queue full)."""
        return self.queue.fence() if self._enabled else None

    # -- configuration -------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled

    def set_enabled(self, on: bool) -> None:
        self._enabled = bool(on)

    def set_analysis(self, on: bool) -> None:
        self._analysis = bool(on)

    def reset_counters(self) -> None:
        """Forget per-entry counters (bench A/B helper); keeps wraps."""
        with self._lock:
            entries = [e for lst in self._programs.values() for e in lst]
        for e in entries:
            e.calls = 0
            e.compiles = 0
            e.call_wall_s = 0.0
            e.compile_wall_s = 0.0
            e.last_compile_s = 0.0
            e.max_call_s = 0.0
            e.done = e.device_ns = e.queue_wait_ns = e.max_device_ns = 0
        self.queue.reset_counters()

    # -- query side ----------------------------------------------------

    def totals(self) -> Dict[str, int]:
        calls = compiles = recompiles = failures = 0
        with self._lock:
            entries = [e for lst in self._programs.values() for e in lst]
        for e in entries:
            calls += e.calls
            compiles += e.compiles
            recompiles += e.recompiles
            failures += e.analysis_error is not None
        return {"programs": len(self._programs), "calls": calls,
                "compiles": compiles, "recompiles": recompiles,
                "analysisFailures": failures}

    def programs(self) -> Dict[str, Dict]:
        """Per-name merged view (several builds of one name sum up)."""
        with self._lock:
            items = {k: list(v) for k, v in self._programs.items()}
        out: Dict[str, Dict] = {}
        for name, entries in sorted(items.items()):
            merged: Dict = {
                "builds": len(entries), "calls": 0, "compiles": 0,
                "recompiles": 0, "callWallMs": 0.0, "compileWallMs": 0.0,
                "lastCompileMs": 0.0, "maxCallMs": 0.0, "done": 0,
                "deviceMs": 0.0, "queueWaitMs": 0.0, "maxDeviceMs": 0.0,
            }
            for e in entries:
                d = e.as_dict()
                for k in ("calls", "compiles", "recompiles", "done"):
                    merged[k] += d[k]
                for k in ("callWallMs", "compileWallMs", "deviceMs",
                          "queueWaitMs"):
                    merged[k] = round(merged[k] + d[k], 3)
                for k in ("lastCompileMs", "maxCallMs", "maxDeviceMs"):
                    merged[k] = max(merged[k], d[k])
                if "analysisWallMs" in d:
                    merged["analysisWallMs"] = round(
                        merged.get("analysisWallMs", 0.0)
                        + d["analysisWallMs"], 3)
                if "cost" in d:
                    merged["cost"] = d["cost"]
                if "memory" in d:
                    merged["memory"] = d["memory"]
                if "analysisError" in d:
                    merged["analysisError"] = d["analysisError"]
            out[name] = merged
        return out

    def status(self, devices: Optional[Sequence] = None) -> Dict:
        """Full dict for the ``/statusz`` device section. ``devices``
        are the devices of the mesh the store actually uses: they name
        the platform the answers came from, and bound the HBM gauges."""
        import jax

        body = {
            "enabled": self._enabled,
            "analysis": self._analysis,
            "totals": self.totals(),
            "programs": self.programs(),
            "queue": self.queue.status(),
            "hbm": hbm_stats(devices),
            # the persistent compile cache JAX is using (None = off)
            "compileCacheDir": jax.config.jax_compilation_cache_dir or None,
        }
        if devices:
            body["platform"] = devices[0].platform
            body["deviceKind"] = devices[0].device_kind
            body["count"] = len(devices)
        try:
            from zipkin_tpu import readpack

            body["transfers"] = {
                "count": readpack.transfer_count(),
                "bytes": readpack.transfer_bytes(),
            }
        except Exception:
            pass
        return body


def hbm_stats(devices: Optional[Sequence] = None) -> Dict:
    """Live accelerator memory, summed and per device, over ``devices``
    (default: all local devices); ``{}`` where the backend exposes no
    ``memory_stats()`` (CPU)."""
    if devices is None:
        import jax

        devices = jax.local_devices()
    per_device = []
    for d in devices:
        stats = d.memory_stats()
        if not stats:
            continue
        per_device.append({
            "id": int(d.id),
            "bytesInUse": int(stats.get("bytes_in_use", 0)),
            "bytesLimit": int(stats.get("bytes_limit", 0)),
            "peakBytesInUse": int(stats.get("peak_bytes_in_use", 0)),
        })
    if not per_device:
        return {}
    return {
        "devices": len(per_device),
        "bytesInUse": sum(d["bytesInUse"] for d in per_device),
        "bytesLimit": sum(d["bytesLimit"] for d in per_device),
        "peakBytesInUse": sum(d["peakBytesInUse"] for d in per_device),
        "perDevice": per_device,
    }


def _env_on(name: str, default: str = "1") -> bool:
    return os.environ.get(name, default).strip().lower() \
        not in ("0", "false", "no")


OBSERVATORY = DeviceObservatory(
    enabled=_env_on("TPU_OBS_DEVICE") and _env_on("TPU_OBS"),
    analysis=_env_on("TPU_OBS_DEVICE_ANALYSIS"),
)

wrap = OBSERVATORY.wrap
