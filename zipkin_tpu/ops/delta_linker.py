"""The roll-up's link context and the persistent ctx it advances.

At each roll-up the device ADVANCES a persistent ctx structure: the
sorted order of the 2n-lane join union, its run decomposition, and
per-run first-wins candidates restricted to lanes that cannot die
before the next advance ("safe" lanes). The advance takes all of it
from the one full-union sort the roll-up's own resolve needs anyway
(the delta is half the ring, so merging it into the stored order by
searched ranks cost seventy sorts' worth of single-element gathers and
saved none), and its resolved tree is the roll-up's emit context.

The roll-up alone reads what this module keeps. A fresh dependency
read resolves from scratch (``tpu/ingest.py:fresh_link_context``, one
sort of the union): on the chip the since-rollup delta read, which
mapped its runs onto the stored ones by two binary searches of the
stored keys, cost 0.87-1.26 s of device time a call at ring 2^18 on a
TPU v5e, against some 30 ms for the union's sort (PERF.md). The leaves
stay in the state and the snapshot until a change of
``SNAPSHOT_VERSION`` takes them out.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from zipkin_tpu.ops import linker


class CtxStruct(NamedTuple):
    """Persistent device ctx over the 2n-lane join union (n ring lanes).

    All leaves live in :class:`zipkin_tpu.tpu.state.AggState` (``ctx_*``)
    and are advanced in :func:`advance` at rollup cadence. ``keys`` is a
    SNAPSHOT of the union sort keys at the last advance: lanes written
    since then ("delta" lanes) have stale rows here.
    """

    order: jnp.ndarray     # i32 [2n] union index at each sorted position
    keys: jnp.ndarray      # u32 [4, 2n] sort-key snapshot per position
    rid_c: jnp.ndarray     # i32 [2n] coarse (trace, id) run id, 1-based
    rid_f: jnp.ndarray     # i32 [2n] fine (trace, id, svc) run id, 1-based
    inv: jnp.ndarray       # i32 [2n] sorted position of union entry u
    safe_sh: jnp.ndarray   # i32 [2n] run-broadcast first SAFE shared lane
    safe_ns: jnp.ndarray   # i32 [2n] ... first SAFE non-shared lane
    safe_fsh: jnp.ndarray  # i32 [2n] ... first SAFE shared lane, fine run
    pos: jnp.ndarray       # i32 [] ring cursor at the last advance
    delta: jnp.ndarray     # i32 [] lanes written since the last advance


def advance(x: linker.LinkInput, cs: CtxStruct, seg: int):
    """Advance the persistent ctx to the ring as it stands.

    Runs at rollup cadence (fused into rollup_step). ONE sort of the
    whole 2n-lane union (:func:`linker.sort_union`, stable, so equal
    keys land in union-index order on every run) serves both halves:
    the rollup's tree, chosen exactly as ``linker.resolve_parents``
    chooses it, and the next ctx — the sorted lanes ARE ``order`` and
    ``keys``, the run ids come with them, and the safe candidates ride
    the same run-min ladder as the tree's three channels. Of the old ctx only ``pos`` and ``delta`` are read.

    Returns (new_ctx, ctx_parent, ctx_anc, ctx_root, link_context): the
    resolved tree doubles as the rollup's emit context, so the rollup
    program stops paying for its own from-scratch link_context.
    """
    n = x.valid.shape[0]
    u = 2 * n
    # host invariant (ShardedAggregator ingest cadence): at most one
    # rollup segment is ever written between advances
    npos = (cs.pos + jnp.clip(cs.delta, 0, seg)) % n
    su = linker.sort_union(x)

    # a table candidate is safe when its age at this advance is >= seg:
    # the cursor cannot reach it before the next one. su.sh / su.ns are
    # below the sentinel exactly on valid (shared / non-shared) table
    # lanes, so the guards need no gather
    age = (jnp.where(su.order < n, su.order, su.order - n) - npos) % n

    def safe(v):
        return jnp.where((v < u) & (age >= seg), age, u)

    safe_sh, safe_ns = safe(su.sh), safe(su.ns)
    mins = linker._run_min_ladder(
        linker.tree_channels(su)
        + [(safe_sh, su.rid_c), (safe_ns, su.rid_c), (safe_sh, su.rid_f)],
        u,
    )
    parent, has_child = linker.choose_parents(x, su, *mins[:3])
    nsafe_sh, nsafe_ns, nsafe_fsh = (
        jnp.where(v >= 0, (npos + v) % n, -1) for v in mins[3:]
    )

    new_cs = CtxStruct(
        order=su.order,
        keys=jnp.stack(su.keys),
        rid_c=su.rid_c, rid_f=su.rid_f,
        inv=jnp.zeros((u,), jnp.int32).at[su.order].set(
            jnp.arange(u, dtype=jnp.int32)
        ),
        safe_sh=nsafe_sh, safe_ns=nsafe_ns, safe_fsh=nsafe_fsh,
        pos=npos.astype(jnp.int32),
        delta=jnp.zeros((), jnp.int32),
    )

    anc, root_ok = linker.chase_ancestors(
        parent, jnp.where(x.valid, x.kind, 0)
    )
    ctx = linker.apply_rules(x, parent, has_child, anc, root_ok)
    return new_cs, parent, anc, root_ok, ctx
