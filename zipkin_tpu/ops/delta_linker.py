"""Incremental link context: the since-rollup delta formulation.

The from-scratch resolve in :mod:`zipkin_tpu.ops.linker` sorts the full
2n-lane join union (~29.6 ms of the 41.3 ms fresh dependency read at
ring 2^18, PROFILE_r05). A fresh read should not pay that, and the
rollup cadence already bounds how much the ring can change between
rollups: the host triggers a rollup before writes since the last one
exceed ``rollup_segment`` (R/2), so at any instant the ring differs from
its state at the last rollup by at most one delta segment. This module
exploits that bound:

- At each rollup the device ADVANCES a persistent ctx structure: the
  sorted union order, its run decomposition, and per-run first-wins
  candidates restricted to lanes that cannot die before the next
  advance ("safe" lanes). The advance REBUILDS the order with the one
  full-union sort the rollup's own resolve needs anyway (the delta is
  half the ring, so merging it into the stored order by searched ranks
  cost seventy sorts' worth of single-element gathers and saved none).
- A fresh read sorts ONLY the 2·rollup_segment delta union, binary
  searches the stored (immutable) keys to map delta runs onto stored
  runs, and resolves every candidate by a three-way age-partition
  priority select. No full-ring sort, no run-min ladder.

Why the partition select is EXACT (bit-identical to the oracle): ring
overwrites always hit the globally-oldest lanes, so with ``Δ =
rollup_segment`` the lanes at advance-age ``[0, Δ)`` ("doomed") are the
only ones that can die before the next advance, and the age order
doomed < safe < delta holds lane-for-lane. First-wins = min insertion
age, so the run's first candidate is: the oldest STILL-ALIVE doomed
candidate if any (recomputed at read over the Δ-lane doomed window),
else the stored safe candidate (immutable between advances), else the
first delta candidate (from the delta sort). No fallback path, no
approximation — parity is fuzzed in tests/test_incremental_ctx.py.

On the READ path everything is width-Δ or width-log(n): the only
full-width ops are elementwise gathers/scatters and the ancestor chase
(pointer doubling is already convergence-bounded and cheap). ZT-lint
rule ZT07 enforces that no full-ring sort/scan creeps back into it; the
full-ring sort lives at rollup cadence alone.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from zipkin_tpu.ops import linker
from zipkin_tpu.ops.segments import segment_starts


class CtxStruct(NamedTuple):
    """Persistent device ctx over the 2n-lane join union (n ring lanes).

    All leaves live in :class:`zipkin_tpu.tpu.state.AggState` (``ctx_*``)
    and are advanced in :func:`advance` at rollup cadence. ``keys`` is a
    SNAPSHOT of the union sort keys at the last advance: lanes written
    since then ("delta" lanes) have stale rows here, but their stored
    entries are dead (masked by age) and run identity of the surviving
    entries never changes — which is what makes the stored arrays
    binary-searchable without maintenance.
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


def init_ctx(n: int) -> CtxStruct:
    """Ctx of an all-invalid ring: every union key is 0xFFFFFFFF, so the
    identity order is validly sorted and the whole union is one run with
    no candidates — exactly what an advance over the empty ring yields."""
    u = 2 * n
    return CtxStruct(
        order=jnp.arange(u, dtype=jnp.int32),
        keys=jnp.full((4, u), 0xFFFFFFFF, jnp.uint32),
        rid_c=jnp.ones((u,), jnp.int32),
        rid_f=jnp.ones((u,), jnp.int32),
        inv=jnp.arange(u, dtype=jnp.int32),
        safe_sh=jnp.full((u,), -1, jnp.int32),
        safe_ns=jnp.full((u,), -1, jnp.int32),
        safe_fsh=jnp.full((u,), -1, jnp.int32),
        pos=jnp.zeros((), jnp.int32),
        delta=jnp.zeros((), jnp.int32),
    )


def _lex_lt(a, b):
    """Elementwise lexicographic a < b over parallel key-lane lists."""
    lt = a[-1] < b[-1]
    for k in range(len(a) - 2, -1, -1):
        lt = (a[k] < b[k]) | ((a[k] == b[k]) & lt)
    return lt


def _lex_eq(a, b):
    eq = a[0] == b[0]
    for k in range(1, len(a)):
        eq = eq & (a[k] == b[k])
    return eq


def _lower_bound(tbl, q):
    """Vectorized binary search: for each query key (parallel lanes in
    ``q``) the leftmost index i in [0, len] with tbl[i] >= q. ``tbl``
    lanes must be lex-sorted. ceil(log2(len))+1 fixed passes of 4-wide
    gathers — the price of mapping a delta run onto the stored run
    universe without touching the full ring."""
    size = int(tbl[0].shape[0])
    m = q[0].shape[0]
    lo = jnp.zeros((m,), jnp.int32)
    hi = jnp.full((m,), size, jnp.int32)

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi) >> 1
        mi = jnp.clip(mid, 0, size - 1)
        t = [lane[mi] for lane in tbl]
        go_right = _lex_lt(t, q)  # tbl[mid] < q
        act = lo < hi
        lo = jnp.where(act & go_right, mid + 1, lo)
        hi = jnp.where(act & ~go_right, mid, hi)
        return lo, hi

    lo, _ = jax.lax.fori_loop(0, max(size.bit_length(), 1), body, (lo, hi))
    return lo


def _resolve_core(x: linker.LinkInput, cs: CtxStruct, seg: int):
    """The fresh read's resolve: (parent, has_child) from the stored ctx
    plus the since-advance delta, paying only for the delta."""
    n = x.valid.shape[0]
    u = 2 * n
    apos = cs.pos
    # host invariant (ShardedAggregator ingest cadence): at most one
    # rollup segment is ever written between advances
    delta = jnp.clip(cs.delta, 0, seg)
    lane_all = jnp.arange(n, dtype=jnp.int32)

    # ---- the delta segment: the ONLY sorted piece, width 2*seg --------
    j = jnp.arange(seg, dtype=jnp.int32)
    dlane = (apos + j) % n
    live_j = j < delta  # lanes actually written since the advance

    def g(col):
        return col[dlane]

    sub = linker.LinkInput(
        trace_h=g(x.trace_h), tl0=g(x.tl0), tl1=g(x.tl1),
        s0=g(x.s0), s1=g(x.s1), p0=g(x.p0), p1=g(x.p1),
        shared=g(x.shared), kind=g(x.kind), svc=g(x.svc),
        rsvc=g(x.rsvc), err=g(x.err), valid=g(x.valid) & live_j,
    )
    d_id, d_svc, d_hasp = linker.union_key_lanes(sub)
    duidx = jnp.arange(2 * seg, dtype=jnp.int32)
    # zt-lint: disable=ZT07 — sorts only the delta segment: 2·Δ union lanes (Δ = rollup_segment = R/2), half the oracle's 2·R full-ring union; the ring-wide order is maintained at rollup cadence by advance()
    sk0, sk1, sk2, sk3, suid = jax.lax.sort(
        tuple(d_id) + (d_svc, duidx), num_keys=4
    )
    dkeys = [sk0, sk1, sk2, sk3]
    sj = suid % seg             # delta-lane index of the sorted entry
    s_isq = suid >= seg         # query-half entry
    slane = dlane[sj]
    s_live = live_j[sj]         # entry belongs to a written delta lane
    s_sh = sub.shared[sj]
    s_tbl_valid = ~s_isq & sub.valid[sj]
    s_q_valid = s_isq & d_hasp[sj]
    s_entry_valid = s_tbl_valid | s_q_valid

    # delta-local run decomposition (contiguous in the delta sort)
    dcoarse = linker._run_starts(dkeys[:3])
    dfine = dcoarse | jnp.asarray(segment_starts(sk3))
    drid_c = jnp.cumsum(dcoarse.astype(jnp.int32))
    drid_f = jnp.cumsum(dfine.astype(jnp.int32))

    # ---- map delta runs onto stored runs (binary search, width 2*seg) -
    skeys = [cs.keys[0], cs.keys[1], cs.keys[2], cs.keys[3]]
    p3 = _lower_bound(skeys[:3], dkeys[:3])
    p4 = _lower_bound(skeys, dkeys)
    p3c = jnp.clip(p3, 0, u - 1)
    p4c = jnp.clip(p4, 0, u - 1)
    m3 = (p3 < u) & _lex_eq([a[p3c] for a in skeys[:3]], dkeys[:3])
    m4 = (p4 < u) & _lex_eq([a[p4c] for a in skeys], dkeys)
    rid_c_old = jnp.where(m3, cs.rid_c[p3c], 0)  # 0 = no stored run
    rid_f_old = jnp.where(m4, cs.rid_f[p4c], 0)

    # delta candidate tables over the EXTENDED run universe: stored run
    # ids [1, u] for matched keys, synthetic ids above u for brand-new
    # keys (so two delta runs of the same new key still share a slot)
    tsz = u + 2 * seg + 1
    rid_c_ext = jnp.where(m3, rid_c_old, u + drid_c)
    rid_f_ext = jnp.where(m4, rid_f_old, u + drid_f)
    bigj = jnp.int32(2 * seg)  # > any delta write index

    def dmin(guard, rid):
        return jnp.full((tsz,), bigj, jnp.int32).at[rid].min(
            jnp.where(guard, sj, bigj)
        )

    dl_sh = dmin(s_tbl_valid & s_sh, rid_c_ext)
    dl_ns = dmin(s_tbl_valid & ~s_sh, rid_c_ext)
    dl_fsh = dmin(s_tbl_valid & s_sh, rid_f_ext)

    # ---- doomed window: first STILL-ALIVE candidate per stored run ----
    # (width seg; slot 0 of each table is never scattered — stored run
    # ids are 1-based — so unmatched gathers read the empty sentinel)
    a = jnp.arange(seg, dtype=jnp.int32)
    alane = (apos + a) % n
    aalive = (a >= delta) & x.valid[alane]
    apos_tbl = cs.inv[alane]  # stored position of the lane's table entry
    arc = cs.rid_c[apos_tbl]
    arf = cs.rid_f[apos_tbl]
    ash = x.shared[alane]
    biga = jnp.int32(seg)  # > any doomed age

    def amin(guard, rid):
        return jnp.full((u + 1,), biga, jnp.int32).at[rid].min(
            jnp.where(guard, a, biga)
        )

    dm_sh = amin(aalive & ash, arc)
    dm_ns = amin(aalive & ~ash, arc)
    dm_fsh = amin(aalive & ash, arf)

    def pick(dmv, safe, dlv):
        # age-partition priority: alive doomed (oldest) > stored safe
        # (middle) > delta (newest); exactness argued in the module doc
        return jnp.where(
            dmv < biga, (apos + dmv) % n,
            jnp.where(
                safe >= 0, safe,
                jnp.where(dlv < bigj, (apos + dlv) % n, -1),
            ),
        )

    def prefer(c_sh, c_ns, c_fsh, is_table, qshf, svc_key):
        # SpanNode._choose_parent preference chain on candidate LANES —
        # the elementwise mirror of resolve_parents' sorted-space select
        prim_ok = c_ns >= 0
        prim_svc = x.svc[jnp.where(prim_ok, c_ns, 0)].astype(jnp.uint32)
        prim_match = prim_ok & (prim_svc == svc_key)
        byp = c_ns
        byp = jnp.where(c_sh >= 0, c_sh, byp)
        byp = jnp.where(prim_match, c_ns, byp)
        byp = jnp.where(c_fsh >= 0, c_fsh, byp)
        return jnp.where(is_table | qshf, c_ns, byp)

    # ---- surviving stored entries (full-width elementwise only) -------
    ou = cs.order
    o_lane = jnp.where(ou < n, ou, ou - n)
    o_isq = ou >= n
    o_age = (o_lane - apos) % n
    o_alive = o_age >= delta  # lanes at age < delta were overwritten
    o_csh = pick(dm_sh[cs.rid_c], cs.safe_sh, dl_sh[cs.rid_c])
    o_cns = pick(dm_ns[cs.rid_c], cs.safe_ns, dl_ns[cs.rid_c])
    o_cfsh = pick(dm_fsh[cs.rid_f], cs.safe_fsh, dl_fsh[cs.rid_f])
    o_qsh = o_isq & x.shared[o_lane] & x.valid[o_lane]
    o_comb = prefer(o_csh, o_cns, o_cfsh, ~o_isq, o_qsh, cs.keys[3])

    # ---- delta entries ------------------------------------------------
    d_csh = pick(dm_sh[rid_c_old], jnp.where(m3, cs.safe_sh[p3c], -1),
                 dl_sh[rid_c_ext])
    d_cns = pick(dm_ns[rid_c_old], jnp.where(m3, cs.safe_ns[p3c], -1),
                 dl_ns[rid_c_ext])
    d_cfsh = pick(dm_fsh[rid_f_old], jnp.where(m4, cs.safe_fsh[p4c], -1),
                  dl_fsh[rid_f_ext])
    d_qsh = s_isq & s_sh & sub.valid[sj]
    d_comb = prefer(d_csh, d_cns, d_cfsh, ~s_isq, d_qsh, sk3)

    # ---- un-scatter: stored entries first, delta overwrites its lanes -
    un = jnp.full((u,), -1, jnp.int32)
    un = un.at[ou].set(jnp.where(o_alive, o_comb, -1))
    d_union_idx = jnp.where(s_isq, n + slane, slane)
    un = un.at[jnp.where(s_live, d_union_idx, u)].set(
        jnp.where(s_entry_valid, d_comb, -1), mode="drop"
    )

    # ---- finish exactly as resolve_parents ----------------------------
    has_parent = ((x.p0 | x.p1) != 0) & x.valid
    sharedv = x.valid & x.shared
    j_shared = jnp.where(sharedv, un[:n], -1)
    q = jnp.where(has_parent, un[n:], -1)
    parent = jnp.where(sharedv, jnp.where(j_shared >= 0, j_shared, q), q)
    parent = jnp.where(parent == lane_all, -1, parent)
    parent = jnp.where(x.valid, parent, -1)
    has_child = (
        jnp.zeros(n, jnp.int32)
        .at[jnp.where(parent >= 0, parent, 0)]
        .max(jnp.where(parent >= 0, 1, 0))
    ).astype(bool)

    return parent, has_child


def delta_resolve(
    x: linker.LinkInput, cs: CtxStruct, seg: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(parent, has_child) — bit-identical to linker.resolve_parents over
    the same ring, paying only the since-advance delta."""
    return _resolve_core(x, cs, seg)


def delta_link_context(
    x: linker.LinkInput, cs: CtxStruct, seg: int
) -> linker.LinkContext:
    """The fresh-read link context via the delta formulation."""
    parent, has_child = _resolve_core(x, cs, seg)
    anc, root_ok = linker.chase_ancestors(
        parent, jnp.where(x.valid, x.kind, 0)
    )
    return linker.apply_rules(x, parent, has_child, anc, root_ok)


def advance(x: linker.LinkInput, cs: CtxStruct, seg: int):
    """Advance the persistent ctx to the ring as it stands.

    Runs at rollup cadence (fused into rollup_step). ONE sort of the
    whole 2n-lane union (:func:`linker.sort_union`, stable, so equal
    keys land in union-index order on every run) serves both halves:
    the rollup's tree, chosen exactly as ``linker.resolve_parents``
    chooses it, and the next ctx — the sorted lanes ARE ``order`` and
    ``keys``, the run ids come with them, and the safe candidates for
    the NEXT doom window ride the same run-min ladder as the tree's
    three channels. Of the old ctx only ``pos`` and ``delta`` are read.

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
