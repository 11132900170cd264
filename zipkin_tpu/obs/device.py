"""Device-program observatory: runtime visibility into jitted programs.

The static lint (ZT03) proves no *avoidable* recompile triggers exist in
the source; this is the dynamic complement. Every jitted/shard_map
entrypoint (ingest step variants, rollup, the spmd_* read programs) is
wrapped at build time in :func:`DeviceObservatory.wrap`, which captures:

- **call count + per-call device wall** (dispatch-to-ready, host view);
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

import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from zipkin_tpu.obs import querytrace

logger = logging.getLogger(__name__)


class ProgramStats:
    """Counters for one wrapped program build (one jit'd callable)."""

    __slots__ = ("name", "calls", "compiles", "call_wall_s",
                 "compile_wall_s", "last_compile_s", "max_call_s",
                 "cache_size", "cost", "memory", "analysis_wall_s",
                 "analysis_error", "_analysis_tried", "_cache_size_fn")

    def __init__(self, name: str, fn: Callable) -> None:
        self.name = name
        self.calls = 0
        self.compiles = 0
        self.call_wall_s = 0.0
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
                analysis: bool) -> Any:
        size_fn = self._cache_size_fn
        before = size_fn() if size_fn is not None else -1
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        dt = time.perf_counter() - t0
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
            "callWallMs": round(self.call_wall_s * 1e3, 3),
            "compileWallMs": round(self.compile_wall_s * 1e3, 3),
            "lastCompileMs": round(self.last_compile_s * 1e3, 3),
            "maxCallMs": round(self.max_call_s * 1e3, 3),
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

    def wrap(self, name: str, fn: Callable) -> Callable:
        """Wrap one jitted callable; transparent when disabled."""
        entry = ProgramStats(name, fn)
        with self._lock:
            self._programs.setdefault(name, []).append(entry)
        obs = self

        def wrapper(*args, **kw):
            if not obs._enabled:
                return fn(*args, **kw)
            return entry.observe(fn, args, kw, obs._analysis)

        wrapper.__name__ = name
        wrapper.__wrapped__ = fn
        wrapper.program_stats = entry
        # AOT path stays reachable (benchmarks lower() programs directly)
        lower = getattr(fn, "lower", None)
        if lower is not None:
            wrapper.lower = lower
        return wrapper

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
                "lastCompileMs": 0.0, "maxCallMs": 0.0,
            }
            for e in entries:
                d = e.as_dict()
                merged["calls"] += d["calls"]
                merged["compiles"] += d["compiles"]
                merged["recompiles"] += d["recompiles"]
                merged["callWallMs"] = round(
                    merged["callWallMs"] + d["callWallMs"], 3)
                merged["compileWallMs"] = round(
                    merged["compileWallMs"] + d["compileWallMs"], 3)
                merged["lastCompileMs"] = max(
                    merged["lastCompileMs"], d["lastCompileMs"])
                merged["maxCallMs"] = max(merged["maxCallMs"], d["maxCallMs"])
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
