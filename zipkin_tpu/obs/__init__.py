"""Pipeline observability: flight recorder + slow-dispatch self-spans.

``RECORDER`` is the process-wide stage recorder; instrumented hot paths
call ``obs.record(stage, dur_s)`` with a stage-name literal from
:mod:`zipkin_tpu.obs.stages` (lint rule ZT08 enforces both the literal
and that no record call hides inside jit'd/device-traced code).
Disable with ``TPU_OBS=0`` — every record becomes one predicate check.

``obs.span(stage, **attrs)`` is ``record`` as a context manager around the
timed block, which also puts the block on the profiler's clock as a trace
event ``zt.<stage>`` (``jax.profiler.TraceAnnotation``; only in a process
that has imported JAX already: see :mod:`zipkin_tpu.obs.recorder`).

``record_relayed`` is the histogram-only sibling for stage walls
measured elsewhere (worker processes) and relayed to the recording
thread — no budget/self-span path, so relayed time is never B3-linked
to the dispatcher's unrelated request context.

``selfspans``, ``windows``, ``device`` and ``slo`` are imported lazily
by the server (they pull in more machinery); low-level modules
importing ``obs`` pay only for the recorder.
"""

import os

from zipkin_tpu.obs.stages import (  # noqa: F401
    DEFAULT_BUDGETS_US,
    NUM_STAGES,
    STAGE_INDEX,
    STAGES,
)
from zipkin_tpu.obs.recorder import (  # noqa: F401
    NUM_BUCKETS,
    Snapshot,
    StageRecorder,
    StageStat,
    bucket_index,
    bucket_le_us,
)

RECORDER = StageRecorder(
    enabled=os.environ.get("TPU_OBS", "1").strip().lower()
    not in ("0", "false", "no"),
)

record = RECORDER.record
span = RECORDER.span
record_relayed = RECORDER.record_relayed
