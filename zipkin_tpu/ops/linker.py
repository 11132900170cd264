"""Windowed dependency linking on device.

The reference computes service dependency links two ways: online tree
walks in ``zipkin2/internal/DependencyLinker.java`` (the InMemory path,
SURVEY.md §3.5) or an **offline batch job** (the zipkin-dependencies Spark
job) writing daily link tables. The TPU design follows the batch shape —
it is the parallel-friendly one — but runs it on-device in milliseconds
over the retained span window, so links are as fresh as the last ingest.

Algorithm over a columnar span window (all arrays fixed-shape ``[n]``):

1. **Parent resolution** — three sort-merge equal-joins on
   (trace, span-id) keys replace the host's hash-map tree build:
   a shared (server-half) span resolves its own id against non-shared
   spans (its client half); a normal span resolves its ``parentId``
   preferring the shared rendition (the server half is the closer tree
   node, matching ``zipkin2/internal/SpanNode.java``'s index preference),
   falling back to non-shared. All joins ride ONE value-carrying
   ``lax.sort`` of the union; per-run first-wins candidates are
   segmented min scans over the contiguous sorted runs — no
   data-dependent control flow, no gather passes.
2. **has-child** marks (scatter-max) implement rule 1 of the linker
   (a CLIENT span with children defers to its server half).
3. **Nearest RPC ancestor** by pointer doubling: ``jump[i]`` points to the
   nearest ancestor-or-self with a kind; squaring it until the fixed
   point (convergence-bounded ``lax.while_loop``, pass count capped at
   ceil(log2 n) so malformed cycles terminate) resolves chains of any
   depth — the device analog of ``_find_rpc_ancestor``'s while-loop.
4. **Rule application** is a pure vectorized select emitting up to two
   edges per span (main + rule-6b backfill), then a scatter-add into the
   ``[services, services]`` call/error matrices — which merge across
   shards by ``psum``.

Parity with the host oracle is asserted span-for-span in
tests/test_ops_linker.py over the DependencyLinkerTest edge-case matrix.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

from zipkin_tpu.ops.segments import segment_starts

def _doubling_passes(n: int) -> int:
    """Pointer-doubling passes needed to resolve ancestor chains of ANY
    depth in an n-lane window: ceil(log2(n+1)). A fixed small cap would
    silently misclassify spans deeper than 2**cap (legit 200-deep retry
    chains exist), dropping their edges."""
    return max((n).bit_length(), 1)

KIND_NONE, KIND_CLIENT, KIND_SERVER, KIND_PRODUCER, KIND_CONSUMER = range(5)


class LinkInput(NamedTuple):
    """Columnar span window (see zipkin_tpu.tpu.columnar.SpanColumns)."""

    trace_h: jnp.ndarray  # u32 hash of the full 128-bit trace id
    tl0: jnp.ndarray  # u32 low lanes of the trace id (join key lanes)
    tl1: jnp.ndarray
    s0: jnp.ndarray  # u32 span id lanes
    s1: jnp.ndarray
    p0: jnp.ndarray  # u32 parent id lanes (0,0 = absent)
    p1: jnp.ndarray
    shared: jnp.ndarray  # bool — server half of a shared-id RPC pair
    kind: jnp.ndarray  # i32 KIND_*
    svc: jnp.ndarray  # i32 local service id (0 = unknown)
    rsvc: jnp.ndarray  # i32 remote service id (0 = unknown)
    err: jnp.ndarray  # bool — span has an "error" tag
    valid: jnp.ndarray  # bool — lane holds a live span
    # insertion sequence: a permutation of [0, n) where LOWER = inserted
    # EARLIER. The host tree builder's tie-breaks are first-wins in
    # insertion order; for a circular ring the lane index stops tracking
    # insertion order after the first wrap, so the ring view derives age
    # from (lane - ring_pos) % R. None (plain batch windows) = lane order.
    seq: jnp.ndarray = None


def _run_starts(key_lanes: Sequence[jnp.ndarray]) -> jnp.ndarray:
    change = jnp.zeros(key_lanes[0].shape[0], bool).at[0].set(True)
    for lane in key_lanes:
        change = change | jnp.asarray(segment_starts(lane))
    return change


def _run_min(values: jnp.ndarray, change: jnp.ndarray, none: int) -> jnp.ndarray:
    """Per-run min of ``values`` over runs delimited by ``change`` (sorted
    lanes). ``none`` is the empty sentinel (values >= none mean absent);
    returns -1 for absent. Values are insertion-sequence ranks (see
    LinkInput.seq), so min = FIRST in insertion order, matching the host
    tree builder's first-wins candidate choice — even after a circular
    ring wraps and lane index stops tracking age."""
    run_id = jnp.cumsum(change.astype(jnp.int32)) - 1
    seg = jnp.full(values.shape[0], none, values.dtype).at[run_id].min(values)
    out = seg[run_id]
    return jnp.where(out >= none, -1, out)


def _run_min_ladder(channel_runs, none: int):
    """Segmented run-min BROADCAST via a flat shift-doubling ladder:
    each channel carries its own run identity; every doubling step is
    one fused elementwise kernel (min over self + left/right neighbor
    at distance d, guarded by run-id equality) over ALL channels.

    This replaces the associative-scan formulation (r5 chip A/B, before
    the chip benchmark existed, not re-read): the scans' tree
    sweeps cost ~15 ms of the 23.6 ms resolve at ring 2^18 and resisted
    every restructuring (channel fusion, reverse=True, forward-only
    dual-sort all measured flat or worse — XLA already CSEs identical
    scans); the ladder's ceil(log2 n) fused steps measure 18.9 ms for
    the whole resolve (-4.7 ms) and 29.6 ms for the full link context
    (-6.6 ms). ``channel_runs`` = [(values, run_id), ...]."""
    n = channel_runs[0][0].shape[0]
    vs = [v for v, _ in channel_runs]
    rids = [r for _, r in channel_runs]
    inf = jnp.int32(none)
    steps = max(int(n - 1).bit_length(), 1)
    for k in range(steps):
        d = 1 << k
        if d >= n:
            break
        new = []
        for v, rid in zip(vs, rids):
            rid_l = jnp.concatenate(
                [jnp.full((d,), -1, jnp.int32), rid[:-d]]
            )
            rid_r = jnp.concatenate(
                [rid[d:], jnp.full((d,), -2, jnp.int32)]
            )
            lv = jnp.concatenate([jnp.full((d,), inf), v[:-d]])
            rv = jnp.concatenate([v[d:], jnp.full((d,), inf)])
            v = jnp.minimum(v, jnp.where(rid == rid_l, lv, inf))
            v = jnp.minimum(v, jnp.where(rid == rid_r, rv, inf))
            new.append(v)
        vs = new
    return [jnp.where(v >= none, -1, v) for v in vs]


def union_key_lanes(x: LinkInput):
    """The four u32 sort-key lanes of the 2n-lane join union (table half
    then query half), invalid lanes keyed 0xFFFFFFFF."""
    has_parent = ((x.p0 | x.p1) != 0) & x.valid
    anyvalid = jnp.concatenate([x.valid, has_parent])

    def lane(t, q):
        return jnp.where(
            anyvalid,
            jnp.concatenate([t.astype(jnp.uint32), q.astype(jnp.uint32)]),
            jnp.uint32(0xFFFFFFFF),
        )

    # Join identity: (trace_h, id). trace_h is a 32-bit avalanche hash of
    # the FULL 128-bit trace id — dropping the exact low-64 lanes from
    # the sort key cuts the lexsort from 6 to 4 passes, and a false join
    # needs a 32-bit trace-hash collision AND a 64-bit span-id match
    # within one ring (~2^-40 per colliding pair; the reference tolerates
    # far larger sketch error elsewhere).
    id_lanes = [
        lane(x.trace_h, x.trace_h),
        lane(x.s0, x.p0),
        lane(x.s1, x.p1),
    ]
    # service lane: table lanes carry their OWN service, query lanes the
    # CHILD's — so a run of the (id, svc) composite matches candidates
    # whose service equals the child's, the endpoint-aware preference of
    # SpanNode._choose_parent. svc is the least-significant sort key, so
    # plain (id) runs stay contiguous and both granularities come from
    # ONE sort.
    svc_lane = lane(x.svc.astype(jnp.uint32), x.svc.astype(jnp.uint32))
    return id_lanes, svc_lane, has_parent


def _seg_min_scan(vals, flags, reverse=False):
    """Segmented inclusive min scan over contiguous runs (reset where
    ``flags``). The scans replace the scatter-min/gather formulation:
    at ring capacity 2^18 the scatter variant measured 59.3 ms for the
    whole resolve vs 23.6 ms with scans (r4 A/B on chip)."""

    def combine(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, jnp.minimum(va, vb))

    if reverse:
        vals = jnp.flip(vals)
        flags = jnp.flip(flags)
    _, v = jax.lax.associative_scan(combine, (flags, vals))
    return jnp.flip(v) if reverse else v


def _run_min_bcast(vals, starts, none):
    """Per-run min broadcast to every lane of the run (sorted contiguous
    runs): forward segmented prefix-min covers [start..lane], backward
    covers [lane..end]; their minimum is the full-run min. ``none`` is
    the empty sentinel; absent runs return -1. Values are insertion-
    sequence ranks (see LinkInput.seq), so min = FIRST in insertion
    order, matching the host tree builder\'s first-wins candidate choice
    even after a circular ring wraps."""
    ends = jnp.concatenate([starts[1:], jnp.ones((1,), bool)])
    fwd = _seg_min_scan(vals, starts)
    bwd = _seg_min_scan(vals, ends, reverse=True)
    out = jnp.minimum(fwd, bwd)
    return jnp.where(out >= none, -1, out)


class SortedUnion(NamedTuple):
    """The 2n-lane join union after its ONE sort: everything downstream
    (run ids, first-wins candidates, the preference chain, and the
    persistent ctx that :func:`zipkin_tpu.ops.delta_linker.advance`
    keeps at roll-up cadence) is contiguous work over these lanes."""

    keys: Tuple[jnp.ndarray, ...]  # 4 x u32 [2n]: trace_h, id0, id1, svc
    sh: jnp.ndarray     # i32 [2n] insertion rank of a shared table lane, else 2n
    ns: jnp.ndarray     # i32 [2n] ... of a non-shared table lane, else 2n
    qsh: jnp.ndarray    # bool [2n] query lane of a shared span
    order: jnp.ndarray  # i32 [2n] union index at each sorted position
    rid_c: jnp.ndarray  # i32 [2n] coarse (trace, id) run id, 1-based
    rid_f: jnp.ndarray  # i32 [2n] fine (trace, id, svc) run id, 1-based


# payload lane of the union sort: the union index below, the span's
# (valid & shared) and (valid & ~shared) flags above it
_SH_BIT, _NS_BIT = 30, 29


def sort_union(x: LinkInput) -> SortedUnion:
    """All three id joins (shared half -> client half, parent-id -> shared
    rendition, parent-id -> non-shared) ride ONE multi-operand
    ``lax.sort`` of a 2n-lane union — table lanes keyed by own
    (trace, span-id), query lanes keyed by (trace, parent-id) — that
    CARRIES the union index and the selection flags through the sort in
    one packed payload lane: the TPU compiler's time for a sort grows
    with its operands (2^19 lanes: 231 s with five, 277 s with six on
    the sandbox's CPUs, PERF.md section 6, PR 33) and the sort is most
    of what a roll-up program takes to compile. The candidate values
    (insertion ranks) follow by one gather. The sort is stable, so equal
    keys keep union-index order: the same ring always sorts to the same
    lanes (snapshots and WAL replay compare them)."""
    n = x.valid.shape[0]
    if 2 * n > 1 << _NS_BIT:
        raise ValueError(
            f"a union of {2 * n} lanes outgrows the payload's index bits"
        )
    id_lanes, svc_lane, _ = union_key_lanes(x)
    flags = ((x.valid & x.shared).astype(jnp.int32) << _SH_BIT) | (
        (x.valid & ~x.shared).astype(jnp.int32) << _NS_BIT
    )
    payload = jnp.arange(2 * n, dtype=jnp.int32) | jnp.concatenate(
        [flags, flags]
    )

    *keys, pay = jax.lax.sort(
        tuple(id_lanes) + (svc_lane, payload), num_keys=4
    )
    sord = pay & ((1 << _NS_BIT) - 1)
    is_sh = (pay & (1 << _SH_BIT)) != 0
    is_ns = (pay & (1 << _NS_BIT)) != 0
    is_table = sord < n
    lane = jnp.where(is_table, sord, sord - n)
    seq_s = lane if x.seq is None else x.seq.astype(jnp.int32)[lane]
    sent = 2 * n  # run-min "absent" sentinel

    coarse = _run_starts(keys[:3])
    fine = coarse | jnp.asarray(segment_starts(keys[3]))
    return SortedUnion(
        keys=tuple(keys),
        sh=jnp.where(is_table & is_sh, seq_s, sent),
        ns=jnp.where(is_table & is_ns, seq_s, sent),
        # the query half carries the span's shared flag so the
        # sorted-space selection can pick fallback-vs-preference without
        # a second unsort
        qsh=~is_table & is_sh,
        order=sord,
        rid_c=jnp.cumsum(coarse.astype(jnp.int32)),
        rid_f=jnp.cumsum(fine.astype(jnp.int32)),
    )


def tree_channels(su: SortedUnion):
    """The three run-min channels of the parent choice, in the order
    :func:`choose_parents` takes their broadcasts: any shared / first
    non-shared / shared with the same service."""
    return [(su.sh, su.rid_c), (su.ns, su.rid_c), (su.sh, su.rid_f)]


def choose_parents(
    x: LinkInput, su: SortedUnion, r_sh_any, r_ns_any, r_sh_fine
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(parent, has_child) from the per-run first-wins candidates of
    :func:`tree_channels`, broadcast over the sorted union."""
    n = x.valid.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    # candidate VALUES are insertion-sequence ranks, not lane indices —
    # run-min then picks the first-INSERTED candidate (host first-wins)
    # regardless of where the ring cursor has wrapped to
    seq = idx if x.seq is None else x.seq.astype(jnp.int32)
    rank_to_idx = jnp.zeros(n, jnp.int32).at[seq].set(idx)
    has_parent = ((x.p0 | x.p1) != 0) & x.valid
    sharedv = x.valid & x.shared
    s_svc = su.keys[3]

    # Parent-id resolution in SpanNode._choose_parent preference order,
    # evaluated PER SORTED LANE: 1) first shared with the child\'s
    # service, 2) the FIRST non-shared (primary_by_id — the host never
    # service-scans non-shared candidates, it checks whether THE first
    # one\'s service matches), 3) first shared any service, 4) the first
    # non-shared regardless. s_svc carries the child\'s service on query
    # lanes (garbage on table lanes — never selected there).
    primary = r_ns_any
    p_idx = rank_to_idx[jnp.where(primary >= 0, primary, 0)]
    primary_svc = x.svc[p_idx].astype(jnp.uint32)
    primary_matches = (primary >= 0) & (primary_svc == s_svc)
    by_parent_id = primary
    by_parent_id = jnp.where(r_sh_any >= 0, r_sh_any, by_parent_id)
    by_parent_id = jnp.where(primary_matches, primary, by_parent_id)
    by_parent_id = jnp.where(r_sh_fine >= 0, r_sh_fine, by_parent_id)

    # per-lane combined candidate: table lanes only ever need the first
    # non-shared of their OWN-id run (the shared->client join); query
    # lanes of SHARED spans need the same of their PARENT-id run (the
    # host builder\'s shared fallback consults only primary_by_id — no
    # endpoint preference); query lanes of normal spans take the full
    # preference chain
    is_table = su.order < n
    combined = jnp.where(is_table | su.qsh, r_ns_any, by_parent_id)

    # ONE unsort: scatter the combined rank, convert rank -> lane index
    inv = jnp.zeros(2 * n, jnp.int32).at[su.order].set(combined)
    un = jnp.where(inv >= 0, rank_to_idx[jnp.where(inv >= 0, inv, 0)], -1)

    # ALL spans with parents query the parent-id join — including shared
    # halves: a shared server span prefers its same-id client half, but
    # when that mate is absent it must fall back to its parentId exactly
    # like SpanNode.Builder does (found by the linker fuzz: a mateless
    # shared span previously became a root and re-attributed its edge)
    j_shared = jnp.where(sharedv, un[:n], -1)
    q = jnp.where(has_parent, un[n:], -1)
    parent = jnp.where(sharedv, jnp.where(j_shared >= 0, j_shared, q), q)
    # a span must not become its own parent (self-parent -> dangling root,
    # as the host builder treats a self-referential choice)
    parent = jnp.where(parent == idx, -1, parent)
    parent = jnp.where(x.valid, parent, -1)

    has_child = (
        jnp.zeros(n, jnp.int32)
        .at[jnp.where(parent >= 0, parent, 0)]
        .max(jnp.where(parent >= 0, 1, 0))
    )
    return parent, has_child.astype(bool)


def resolve_parents(x: LinkInput) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Tree edges from id joins: returns (parent_row [n] with -1 for roots,
    has_child [n] bool).

    Everything after :func:`sort_union`'s one sort is contiguous: run
    boundaries are adjacent-lane compares, per-run first-wins candidates
    are segmented min broadcasts, and the SpanNode._choose_parent
    preference chain is evaluated in sorted space so only ONE combined
    candidate needs un-permuting.

    That shape is the r4 redesign of the fresh dependency read
    (VERDICT r3 order 1): the r3 formulation un-permuted three
    candidate arrays through gather/scatter passes and fixed-schedule
    pointer chases, costing 145.8 ms captured device time at ring
    capacity 2^18; this one measures 23.6 ms for the resolve and
    34.3 ms for the full link context (chip A/B, bit-identical output).
    """
    su = sort_union(x)
    # all three run-min broadcasts ride ONE shift-doubling ladder
    mins = _run_min_ladder(tree_channels(su), 2 * x.valid.shape[0])
    return choose_parents(x, su, *mins)


def chase_ancestors(
    parent: jnp.ndarray, kind: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Both pointer-doubling chases of the link rules in ONE
    convergence-bounded loop: returns (anc [n] — nearest strict ancestor
    with a kind, else -1; root_ok [n] bool — the parent chain terminates
    at a root).

    Doubling squares two pointer arrays per pass: ``root`` chases
    ``parent`` toward the sentinel, ``jump`` chases the
    nearest-kinded-ancestor-or-self relation. A fixed
    ceil(log2(n)) schedule costs 19 passes at ring capacity 2^18 —
    70.6 ms captured device time, HALF the 145.8 ms fresh link-context
    rebuild (r3 profile of the rebuild's parts) — yet real trace forests
    are tens deep, converged after 5-8 passes. The lax.while_loop stops
    at the fixed point (captured: 10.7 ms, 6.6x) and stays EXACT for
    any depth: the fixed pass count remains as a bound only so
    malformed parent CYCLES (which never reach a fixed point — a
    3-cycle's pointers orbit forever) still terminate; capped cyclic
    lanes end mid-cycle, never at the sentinel, so ``root_ok`` stays
    False for them exactly as the host tree builder's reachability
    does (found by the linker fuzz).
    """
    n = parent.shape[0]
    sent = n
    par = jnp.where(parent >= 0, parent, sent)
    kind_ext = jnp.concatenate([kind, jnp.zeros((1,), kind.dtype)])
    par_ext = jnp.concatenate([par, jnp.full((1,), sent, par.dtype)])

    # jump[i] = i if span i has a kind, else its parent (toward the root)
    jump = jnp.where(kind_ext != 0, jnp.arange(n + 1), par_ext)
    jump = jump.at[sent].set(sent)
    root = par_ext
    max_passes = _doubling_passes(n)

    def cond(c):
        i, _, _, changed = c
        return changed & (i < max_passes)

    def body(c):
        i, jump, root, _ = c
        j2 = jump[jump]
        r2 = root[root]
        changed = jnp.any(j2 != jump) | jnp.any(r2 != root)
        return i + 1, j2, r2, changed

    # initial `changed` derives from the (possibly shard-varying) data so
    # the while carry types stay consistent under shard_map; jump holds
    # only non-negative lane ids, so this is always True
    _, jump, root, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jump, root, jnp.any(jump >= 0))
    )

    anc = jump[par]  # start the walk at the parent (strict ancestor)
    anc = jnp.where(anc == sent, -1, anc)
    # if the chain ended on a kindless root, there is no RPC ancestor
    anc = jnp.where(
        (anc >= 0) & (kind_ext[jnp.where(anc >= 0, anc, 0)] != 0), anc, -1
    )
    return anc, root[:n] == sent


def reaches_root(parent: jnp.ndarray) -> jnp.ndarray:
    """[n] bool: the parent chain terminates at a root (any depth).
    Malformed cyclic subgraphs (e.g. a span pair parenting each other
    through a shared-id join) never terminate — the host tree builder
    leaves them unreachable from the synthetic root, so its traversal
    never emits their links; this mask is the device analog (found by
    the linker fuzz)."""
    _, ok = chase_ancestors(parent, jnp.zeros_like(parent))
    return ok


def nearest_rpc_ancestor(
    parent: jnp.ndarray, kind: jnp.ndarray
) -> jnp.ndarray:
    """Row index of the nearest strict ancestor with a kind, else -1."""
    anc, _ = chase_ancestors(parent, kind)
    return anc


class LinkContext(NamedTuple):
    """Window-INDEPENDENT link evaluation of a span window: everything
    expensive (the parent join sort, pointer-doubling ancestors,
    reachability) distilled to per-lane edge candidates. Cache one per
    state version and apply any number of cheap windowed emits against
    it (zipkin_tpu.parallel.sharded caches it per write_version — the
    dependency query then costs an elementwise mask + scatter, not a
    re-sort of the ring)."""

    par_svc: jnp.ndarray  # i32 — main edge parent service (post rule 6)
    child_svc: jnp.ndarray  # i32 — main edge child service
    ok: jnp.ndarray  # bool — main edge passes every non-window rule
    err: jnp.ndarray  # bool — ok and the span carries an error tag
    anc_svc: jnp.ndarray  # i32 — nearest RPC ancestor service
    local: jnp.ndarray  # i32 — local service (rule 6b child)
    back: jnp.ndarray  # bool — rule 6b backfill passes non-window rules


def link_context(x: LinkInput) -> LinkContext:
    """Evaluate all link rules except the time window.

    Parent/ancestor joins run over every ``x.valid`` lane, so a windowed
    query still resolves tree context from outside the window — matching
    the reference's whole-trace linking (InMemory getDependencies links
    full traces whose span timestamps intersect the window, SURVEY.md
    §3.5).

    This is the FROM-SCRATCH formulation (one union sort + the run-min
    ladder + the chase), and production fresh reads run it as it is
    (``tpu/ingest.py:fresh_link_context``). The roll-up's advance
    (ops/delta_linker.py) must match it bit-for-bit, and every parity
    test fuzzes against it.
    """
    parent, has_child = resolve_parents(x)
    anc, root_ok = chase_ancestors(parent, jnp.where(x.valid, x.kind, 0))
    return apply_rules(x, parent, has_child, anc, root_ok)


def apply_rules(
    x: LinkInput,
    parent: jnp.ndarray,
    has_child: jnp.ndarray,
    anc: jnp.ndarray,
    root_ok: jnp.ndarray,
) -> LinkContext:
    """The pure elementwise rule half of :func:`link_context`: turn a
    resolved tree (parent rows, child marks, nearest-RPC ancestors,
    root reachability) into per-lane edge candidates. Shared verbatim by
    the from-scratch resolve and the incremental delta resolve so the
    two can only diverge in tree resolution, never in rule semantics."""
    anc_svc = jnp.where(anc >= 0, x.svc[jnp.where(anc >= 0, anc, 0)], 0)

    local, remote = x.svc, x.rsvc
    kind = x.kind

    # rule 1: client span with children defers to its server half;
    # spans in parent cycles never emit (host-traversal reachability)
    live = x.valid & root_ok
    live = live & ~((kind == KIND_CLIENT) & has_child)
    # rule 2: kindless spans with both sides known act like clients
    keff = jnp.where(
        (kind == KIND_NONE) & (local > 0) & (remote > 0), KIND_CLIENT, kind
    )
    live = live & (keff != KIND_NONE)

    is_server_like = (keff == KIND_SERVER) | (keff == KIND_CONSUMER)
    par_svc = jnp.where(is_server_like, remote, local)
    child_svc = jnp.where(is_server_like, local, remote)

    # rule 3: root server with unknown caller
    live = live & ~((keff == KIND_SERVER) & (parent < 0) & (remote == 0))

    is_messaging = (keff == KIND_PRODUCER) | (keff == KIND_CONSUMER)
    # rule 5: messaging needs both sides known, no tree walk through brokers
    live = live & ~(is_messaging & ((par_svc == 0) | (child_svc == 0)))

    # rule 6: RPC spans resolve the parent via the nearest RPC ancestor
    is_rpc = (keff == KIND_CLIENT) | (keff == KIND_SERVER)
    use_anc = is_rpc & (anc_svc > 0) & ((keff == KIND_SERVER) | (par_svc == 0))
    par_svc = jnp.where(use_anc, anc_svc, par_svc)

    main_ok = live & (par_svc > 0) & (child_svc > 0)

    # rule 6b: client whose service differs from its RPC ancestor implies an
    # uninstrumented hop — backfill ancestor->client (never an error)
    back_ok = (
        live
        & (keff == KIND_CLIENT)
        & (local > 0)
        & (anc_svc > 0)
        & (anc_svc != local)
    )
    return LinkContext(
        par_svc=par_svc, child_svc=child_svc, ok=main_ok,
        err=main_ok & x.err, anc_svc=anc_svc, local=local, back=back_ok,
    )


def link_edges(x: LinkInput, emit: jnp.ndarray = None):
    """Per-lane link-rule evaluation with an emit mask applied: returns
    (par_svc, child_svc, main_ok, main_err, anc_svc, local, back_ok)."""
    if emit is None:
        emit = x.valid
    ctx = link_context(x)
    return (
        ctx.par_svc, ctx.child_svc, ctx.ok & emit, ctx.err & emit,
        ctx.anc_svc, ctx.local, ctx.back & emit,
    )


def emit_links(
    ctx: LinkContext, emit: jnp.ndarray, num_services: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter a context's edges for the lanes in ``emit`` — the cheap
    half of a windowed dependency query (no sorts, no joins)."""
    s = num_services
    calls = jnp.zeros((s, s), jnp.uint32)
    errors = jnp.zeros((s, s), jnp.uint32)
    pc = jnp.clip(ctx.par_svc, 0, s - 1)
    cc = jnp.clip(ctx.child_svc, 0, s - 1)
    calls = calls.at[pc, cc].add((ctx.ok & emit).astype(jnp.uint32))
    errors = errors.at[pc, cc].add((ctx.err & emit).astype(jnp.uint32))
    bc = jnp.clip(ctx.anc_svc, 0, s - 1)
    lc = jnp.clip(ctx.local, 0, s - 1)
    calls = calls.at[bc, lc].add((ctx.back & emit).astype(jnp.uint32))
    return calls, errors


def link_window(
    x: LinkInput, num_services: int, emit: jnp.ndarray = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dependency links over one span window.

    Returns (calls, errors) — ``[num_services, num_services]`` uint32
    matrices indexed by interned service id (0 = unknown; row/col 0 is
    never emitted). Merge across shards/windows by addition (psum).
    """
    if emit is None:
        emit = x.valid
    return emit_links(link_context(x), emit, num_services)


def link_window_bucketed(
    x: LinkInput,
    num_services: int,
    slot: jnp.ndarray,
    num_slots: int,
    emit: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Same rules, but each emitting span scatters its edges into the
    time-bucket ``slot[i]`` of its OWN timestamp — the device form of the
    reference's per-day dependency rollup (links attributed to the day of
    the child span, SURVEY.md §2.3 cassandra ``dependency`` table)."""
    return emit_links_bucketed(
        link_context(x), slot, num_slots, emit, num_services
    )


def emit_links_bucketed(
    ctx: LinkContext,
    slot: jnp.ndarray,
    num_slots: int,
    emit: jnp.ndarray,
    num_services: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The cheap scatter half of :func:`link_window_bucketed` against a
    precomputed context — the rollup reuses the incremental advance's
    resolve instead of paying a second from-scratch link_context."""
    s = num_services
    d = jnp.clip(slot.astype(jnp.int32), 0, num_slots - 1)
    calls = jnp.zeros((num_slots, s, s), jnp.uint32)
    errors = jnp.zeros((num_slots, s, s), jnp.uint32)
    pc = jnp.clip(ctx.par_svc, 0, s - 1)
    cc = jnp.clip(ctx.child_svc, 0, s - 1)
    calls = calls.at[d, pc, cc].add((ctx.ok & emit).astype(jnp.uint32))
    errors = errors.at[d, pc, cc].add((ctx.err & emit).astype(jnp.uint32))
    bc = jnp.clip(ctx.anc_svc, 0, s - 1)
    lc = jnp.clip(ctx.local, 0, s - 1)
    calls = calls.at[d, bc, lc].add((ctx.back & emit).astype(jnp.uint32))
    return calls, errors
