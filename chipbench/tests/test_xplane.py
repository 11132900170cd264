"""The trace reducer: its arithmetic on made-up intervals, and the whole
reduction on a small recorded trace (150 ms of one v5e chip under
``bulk.saturate``, PR 24: the device plane's lines as the profiler named
them, the host plane cut down to its extent)."""

import json
import os

import pytest

import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_counts_overlap_once():
    assert xplane.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert xplane.union_seconds([]) == 0
    assert xplane.union_seconds([(0, 10), (2, 3)]) == 10


def test_names():
    assert xplane.program_name("jit_spmd_edges_fresh(4523814815225261454)") \
        == "spmd_edges_fresh"
    assert xplane.program_name("jit_spmd(4293730847295770637)") == "spmd"
    assert xplane.op_name(
        "%while.14 = (s32[]{:T(128)}, s32[524288]{0:T(1024)S(1)}) "
        "while((s32[]{:T(128)}) %tuple.283), condition=%c, body=%b") \
        == "%while.14 while"


def test_gaps_say_what_ran_around_them():
    progs = [(0.0, 1.0, "a"), (3.0, 4.0, "b")]
    ops = [(0.0, 0.4), (0.6, 1.0), (3.0, 4.0)]
    assert xplane.gaps(ops, progs) == [
        ("inside a", pytest.approx(0.2)), ("after a, before b", pytest.approx(2.0))]


def test_made_up_planes():
    planes = [
        ("/host:CPU", [("t", [("x", 10.0, 4.0)])]),
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_spmd(1)", 10.5, 1.0), ("jit_spmd(2)", 12.0, 1.0),
                             ("jit_spmd_card(3)", 13.5, 0.25)]),
            ("XLA Ops", [("%a = f32[] add(f32[] %x)", 10.5, 0.5),
                         ("%b = f32[] fusion(f32[] %x)", 11.0, 0.5),
                         ("%a = f32[] add(f32[] %x)", 12.0, 1.0),
                         ("%c = f32[] sort(f32[] %x)", 13.5, 0.25)]),
        ]),
    ]
    r = xplane.reduce_planes(planes)
    assert r["window_s"] == 4.0 and r["busy_s"] == 2.25
    assert r["programs"] == {"spmd": {"count": 2, "seconds": 2.0},
                             "spmd_card": {"count": 1, "seconds": 0.25}}
    assert r["device_ops"][0] == ["%a add", 1.5]
    assert dict(map(tuple, r["idle_gaps"])) == {
        "after spmd, before spmd (x1)": 0.5,
        "after spmd, before spmd_card (x1)": 0.5}


def test_recorded_trace():
    with open(os.path.join(HERE, "data_trace_v5e_small.json")) as f:
        small = json.load(f)
    planes = [(n, [(ln, [tuple(e) for e in evs]) for ln, evs in lines])
              for n, lines in small]
    assert [n for n, _ in planes if xplane.DEVICE_PLANE.match(n)] \
        == ["/device:TPU:0"]
    r = xplane.reduce_planes(planes)
    assert r["device_planes"] == 1
    assert r["window_s"] == pytest.approx(0.15)
    assert r["busy_s"] == pytest.approx(0.03651675, rel=1e-6)
    assert r["programs"]["spmd"]["count"] == 2
    assert r["programs"]["spmd"]["seconds"] == pytest.approx(0.011272763)
    assert set(r["programs"]) == {"spmd", "spmd_card", "convert_element_type"}
    assert r["device_ops"][0][0] == "%fusion.211 fusion"
    idle = sum(s for _, s in xplane.gaps(
        [(s, s + d) for _, s, d in planes[1][1][2][1]],
        [(s, s + d, xplane.program_name(n)) for n, s, d in planes[1][1][1][1]]))
    # busy + idle between the first and the last op = their span
    ops = planes[1][1][2][1]
    span = max(s + d for _, s, d in ops) - min(s for _, s, _ in ops)
    assert r["busy_s"] + idle == pytest.approx(span)
