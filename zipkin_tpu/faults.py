"""Crashpoint + corruption fault injection for the durability plane.

The durability claims in ARCHITECTURE.md ("every 202-acked batch
replays after kill -9") are only as good as the crash *timing* they
were tested under. This registry names the exact instants inside the
write path where a crash is most likely to tear on-disk state, so the
chaos driver (tests/test_chaos_recovery.py)
can kill the process AT each of them instead of at whatever instant a
timer happens to land on:

- ``wal.append.mid``       header+meta of a WAL record written, payload not
- ``wal.append.pre_fsync`` record fully written+flushed, fsync still pending
- ``snapshot.post_state``  state ``.npz`` renamed in, meta.json not yet
- ``snapshot.post_meta``   meta.json renamed in, covered WAL not yet truncated
- ``archive.mid_segment``  archive frame header+index written, payload not

Arming is either programmatic (``arm(site, nth=..., action=...)`` from
an in-process test) or via the environment for subprocess drivers:
``ZT_CRASHPOINT=<site>[:nth][,<site>[:nth]...]`` fires each listed
site on its nth pass (default 1st); ``ZT_CRASHPOINT_ACTION`` picks
``kill`` (SIGKILL — maximum realism, buffered bytes are lost), ``exit``
(``os._exit`` — kills the process but buffered C-level file writes
already made are kept), or ``raise`` (``CrashpointTriggered`` —
in-process simulation; the caller must abandon the store object,
exactly like the existing ``del victim`` crash idiom in
tests/test_wal.py). Multiple sites arm at once so the corruption soak
can combine a corrupt site with a kill site in one child run.

The ``corrupt`` action family (ISSUE 7) models silent media bit-rot
rather than a crash: a corrupt site names an artifact the write path
just made durable, and firing it damages those bytes ON DISK — the
process keeps running, exactly like rot that happens at rest:

- ``snapshot.state``  the newest committed snapshot generation's .npz
- ``wal.record``      the payload of the WAL record just appended
- ``archive.frame``   the payload of the archive frame just appended

Damage modes are deterministic (position derived from the artifact's
byte range, no RNG): ``flip`` XORs one mid-range byte, ``zero`` zeroes
a mid-range run, ``truncate`` cuts the file mid-artifact. Armed via
``arm_corrupt(site, mode=..., nth=...)`` or
``ZT_CORRUPT=<site>[:mode[:nth]]`` (comma-separated like
ZT_CRASHPOINT). Restore-time digest verification, generation fallback,
and the background scrubber (runtime/scrub.py) are the recovery story
these sites exist to prove.

The ``resource`` family (ISSUE 13) models exhaustion rather than a
crash or rot: the process keeps running but an operation fails (or
slows) the way it does when a machine runs out of something. Sites
name the operation whose resource ran out:

- ``wal.append``   ENOSPC on the WAL record write
- ``snapshot``     ENOSPC on the snapshot state/meta write
- ``archive``      ENOSPC on the archive segment append
- ``feed.latency`` injected latency on the device-feed dispatch
- ``alloc``        allocation failure (MemoryError) on ingest staging

Unlike crashpoints a resource fault is usually *sustained* — a full
disk stays full — so arming takes a ``count``: the site starts firing
on its ``nth`` traversal and keeps firing for ``count`` consecutive
traversals before auto-clearing (space freed). ``count=0`` means fire
until ``disarm()``. Armed via ``arm_resource(site, nth=..., count=...,
latency_ms=...)`` or ``ZT_RESOURCE=<site>[:nth[:count]],...`` (plus
``ZT_RESOURCE_LATENCY_MS`` for the latency site). The handling
contract these sites exist to prove (tests/test_overload.py): disk
exhaustion degrades to an explicitly-flagged at-risk mode with an SLO
page — never a crash, never a silent ack — and clearing the fault
restores normal operation with bit-identical query state.

A resource site can additionally target ONE tenant (ISSUE 18):
``arm_resource(site, tenant="B")`` or
``ZT_RESOURCE=feed.latency:tenant=B`` fires only on traversals
attributed to that tenant — either the explicit ``tenant=`` argument
the call site passes (the fan-out dispatcher knows its chunk's
tenant), or the ambient ``CURRENT_TENANT`` contextvar at boundary
sites. Non-matching traversals do NOT consume ``nth``/``count``, so a
fault armed for tenant B stays armed through any amount of A/C
traffic — the deterministic per-tenant injection the isolation tests
(tests/test_tenant.py) are built on.

The disarmed fast path is one dict probe, so production code keeps the
hooks compiled in; a site is one-shot — it disarms itself as it fires
so crash/scrub *handling* code can re-enter the same path.
"""

from __future__ import annotations

import errno
import logging
import os
import signal
import time
from typing import Dict, List, Optional

logger = logging.getLogger(__name__)

# the site catalogs are static so drivers can randomize over them
SITES = (
    "wal.append.mid",
    "wal.append.pre_fsync",
    "snapshot.post_state",
    "snapshot.post_meta",
    "archive.mid_segment",
    # time-tier bucket seal (tpu/timetier.py): pre_commit fires after
    # the segment tmp file is written but BEFORE the atomic rename
    # (crash leaves no segment — reseal on resume); post_commit fires
    # after the rename but before sealed_through advances (crash leaves
    # a committed segment the resume must adopt idempotently)
    "timetier.seal.pre_commit",
    "timetier.seal.post_commit",
)
CORRUPT_SITES = (
    "snapshot.state",
    "wal.record",
    "archive.frame",
    "timetier.segment",
)
CORRUPT_MODES = ("flip", "truncate", "zero")
RESOURCE_SITES = (
    "wal.append",
    "snapshot",
    "archive",
    "feed.latency",
    "alloc",
)

ENV_VAR = "ZT_CRASHPOINT"
ENV_ACTION = "ZT_CRASHPOINT_ACTION"
ENV_CORRUPT = "ZT_CORRUPT"
ENV_RESOURCE = "ZT_RESOURCE"
ENV_RESOURCE_LATENCY = "ZT_RESOURCE_LATENCY_MS"
EXIT_CODE = 137  # what a SIGKILL'd child reports; `exit` mimics it

_ACTIONS = ("kill", "exit", "raise")


class CrashpointTriggered(RuntimeError):
    """Raised by a crashpoint armed with action="raise". The process is
    notionally dead at this instant: the owning store/WAL/archive object
    must be abandoned, not used further."""


# site -> [remaining_nth, action]; mutated in place by crashpoint()
_armed: Dict[str, List] = {}
# site -> [remaining_nth, mode]; mutated in place by corrupt_point()
_corrupt_armed: Dict[str, List] = {}
# site -> [remaining_nth, remaining_count, latency_s, tenant|None];
# mutated in place by resource_point()
_resource_armed: Dict[str, List] = {}


def arm(site: str, nth: int = 1, action: str = "kill") -> None:
    """Arm one site to fire on its ``nth`` traversal. Arming a second
    site keeps the first armed (multi-site soaks)."""
    if site not in SITES:
        raise ValueError(f"unknown crashpoint site {site!r} (see faults.SITES)")
    if action not in _ACTIONS:
        raise ValueError(f"unknown crashpoint action {action!r}")
    _armed[site] = [max(1, int(nth)), action]


def arm_corrupt(site: str, mode: str = "flip", nth: int = 1) -> None:
    """Arm a corruption site to damage its ``nth`` written artifact."""
    if site not in CORRUPT_SITES:
        raise ValueError(
            f"unknown corrupt site {site!r} (see faults.CORRUPT_SITES)"
        )
    if mode not in CORRUPT_MODES:
        raise ValueError(
            f"unknown corrupt mode {mode!r} (see faults.CORRUPT_MODES)"
        )
    _corrupt_armed[site] = [max(1, int(nth)), mode]


def arm_resource(site: str, nth: int = 1, count: int = 1,
                 latency_ms: float = 25.0,
                 tenant: Optional[str] = None) -> None:
    """Arm a resource site: starts failing on its ``nth`` traversal and
    keeps failing for ``count`` consecutive traversals (0 = until
    ``disarm()``), modeling sustained exhaustion that later clears.
    ``tenant`` scopes the fault to one tenant's traversals (ISSUE 18);
    other tenants pass through without consuming nth/count."""
    if site not in RESOURCE_SITES:
        raise ValueError(
            f"unknown resource site {site!r} (see faults.RESOURCE_SITES)"
        )
    _resource_armed[site] = [
        max(1, int(nth)), max(0, int(count)), max(0.0, latency_ms) / 1000.0,
        tenant or None,
    ]


def disarm() -> None:
    _armed.clear()
    _corrupt_armed.clear()
    _resource_armed.clear()


def armed_site() -> Optional[str]:
    """First armed crashpoint site (None when disarmed). With several
    sites armed, drivers that need the full set should consult their
    own arming calls; this keeps the single-site API working."""
    return next(iter(_armed), None)


def is_armed(site: str) -> bool:
    return site in _armed


def is_corrupt_armed(site: str) -> bool:
    return site in _corrupt_armed


def is_resource_armed(site: str) -> bool:
    return site in _resource_armed


def crashpoint(site: str) -> None:
    """Hot-path hook. No-op (one dict probe) unless ``site`` is armed."""
    spec = _armed.get(site)
    if spec is None:
        return
    spec[0] -= 1
    if spec[0] > 0:
        return
    del _armed[site]  # one-shot: recovery code may re-enter this same path
    action = spec[1]
    logger.warning("crashpoint %s firing (action=%s)", site, action)
    if action == "kill":
        os.kill(os.getpid(), signal.SIGKILL)
    if action == "exit":
        os._exit(EXIT_CODE)
    raise CrashpointTriggered(site)


def corrupt_point(site: str, path: str, start: int, length: int) -> bool:
    """Write-path hook: the caller just made ``length`` bytes at
    ``start`` of ``path`` durable. If ``site`` is armed, damage them in
    place (deterministically) and return True; the caller continues
    normally — rot is silent. One-shot like crashpoints."""
    spec = _corrupt_armed.get(site)
    if spec is None or length <= 0:
        return False
    spec[0] -= 1
    if spec[0] > 0:
        return False
    del _corrupt_armed[site]
    mode = spec[1]
    mid = start + length // 2
    logger.warning(
        "corrupt point %s firing (mode=%s) on %s [%d:+%d]",
        site, mode, path, start, length,
    )
    if mode == "truncate":
        os.truncate(path, mid)
        return True
    with open(path, "r+b") as fh:
        if mode == "flip":
            fh.seek(mid)
            b = fh.read(1)
            fh.seek(mid)
            fh.write(bytes([b[0] ^ 0xFF]) if b else b"\xff")
        else:  # zero
            run = min(256, max(1, length // 3))
            fh.seek(start + length // 3)
            fh.write(b"\x00" * run)
        fh.flush()
        os.fsync(fh.fileno())
    return True


def resource_point(site: str, tenant: Optional[str] = None) -> None:
    """Hot-path hook for exhaustion sites. No-op (one dict probe)
    unless armed. Disk sites raise ``OSError(ENOSPC)``, ``alloc``
    raises ``MemoryError``, ``feed.latency`` sleeps and returns — the
    caller's normal error handling IS the behavior under test.

    When the armed spec names a tenant, only that tenant's traversals
    fire (and count): ``tenant`` is the caller's explicit attribution,
    falling back to the ambient ``CURRENT_TENANT`` contextvar at
    boundary sites where the request context is still live."""
    spec = _resource_armed.get(site)
    if spec is None:
        return
    want = spec[3] if len(spec) > 3 else None
    if want is not None:
        if tenant is None:
            # lazy import: faults must stay importable before runtime/
            from zipkin_tpu.runtime.tenant import CURRENT_TENANT
            tenant = CURRENT_TENANT.get()
        if tenant != want:
            return  # other tenants pass through, nth/count untouched
    if spec[0] > 1:
        spec[0] -= 1  # not yet at the nth traversal
        return
    if spec[1] > 0:
        spec[1] -= 1
        if spec[1] == 0:
            del _resource_armed[site]  # exhaustion cleared (space freed)
    if site == "feed.latency":
        logger.warning("resource fault %s firing (sleep %.1f ms)",
                       site, spec[2] * 1000.0)
        time.sleep(spec[2])
        return
    logger.warning("resource fault %s firing", site)
    if site == "alloc":
        raise MemoryError(f"injected allocation failure at {site}")
    raise OSError(errno.ENOSPC, f"injected ENOSPC at {site}")


def _arm_from_env() -> None:
    raw = os.environ.get(ENV_VAR)
    if raw:
        action = os.environ.get(ENV_ACTION, "kill").strip() or "kill"
        for spec in raw.split(","):
            spec = spec.strip()
            if not spec:
                continue
            site, _, nth = spec.partition(":")
            try:
                arm(site.strip(), int(nth) if nth.strip() else 1, action)
            except ValueError as e:
                # a typo'd env var must not brick a production boot
                logger.warning("ignoring %s=%r: %s", ENV_VAR, raw, e)
    raw = os.environ.get(ENV_CORRUPT)
    if raw:
        for spec in raw.split(","):
            spec = spec.strip()
            if not spec:
                continue
            parts = spec.split(":")
            try:
                arm_corrupt(
                    parts[0].strip(),
                    parts[1].strip() if len(parts) > 1 and parts[1].strip()
                    else "flip",
                    int(parts[2]) if len(parts) > 2 and parts[2].strip()
                    else 1,
                )
            except ValueError as e:
                logger.warning("ignoring %s=%r: %s", ENV_CORRUPT, raw, e)
    raw = os.environ.get(ENV_RESOURCE)
    if raw:
        try:
            lat_ms = float(os.environ.get(ENV_RESOURCE_LATENCY, "25"))
        except ValueError:
            lat_ms = 25.0
        for spec in raw.split(","):
            spec = spec.strip()
            if not spec:
                continue
            parts = spec.split(":")
            tenant = None
            pos = []
            for p in parts[1:]:
                p = p.strip()
                if p.startswith("tenant="):
                    tenant = p[len("tenant="):] or None
                elif p:
                    pos.append(p)
            try:
                arm_resource(
                    parts[0].strip(),
                    int(pos[0]) if len(pos) > 0 else 1,
                    int(pos[1]) if len(pos) > 1 else 1,
                    latency_ms=lat_ms,
                    tenant=tenant,
                )
            except ValueError as e:
                logger.warning("ignoring %s=%r: %s", ENV_RESOURCE, raw, e)


_arm_from_env()
