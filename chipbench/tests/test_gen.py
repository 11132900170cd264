"""The generator: same seed -> same bytes; no trace crosses a POST; POST
sizes divide the rolled half-ring (131,072 lanes)."""

import glob
import json
import os

import numpy as np
import pytest

import gen

HERE = os.path.dirname(os.path.abspath(__file__))
FLEET = {"services": 40, "span_names": 120, "trace_depth": [1, 4],
         "duration_pareto_alpha": 1.2, "error_share": 0.02}


def traffic(seed, spans=1024, templates=3):
    return gen.Traffic(seed, FLEET, {"spans": spans, "templates": templates})


def test_same_seed_same_bytes_other_seed_other_bytes():
    a, b, c = traffic(2_147_483_659), traffic(2_147_483_659), traffic(7)
    for n in (0, 1, 5, 4000):
        assert a.body(n) == b.body(n)
        assert a.body(n) != c.body(n)
    assert a.body(3) != a.body(4)


def test_scratch_buffers_give_the_same_bytes():
    t, scratch = traffic(11), {}
    for n in (0, 1, 2, 3, 4, 5, 6):
        assert t.body(n, scratch) == t.body(n)


def test_no_trace_crosses_a_post_and_no_id_repeats():
    t = traffic(5)
    seen_traces, seen_spans = set(), set()
    for n in range(12):
        spans = json.loads(t.body(n))
        assert len(spans) == 1024
        ids = {s["id"] for s in spans}
        assert len(ids) == len(spans)
        for s in spans:  # every parent is in the same body, same trace
            if "parentId" in s:
                assert s["parentId"] in ids
        by_id = {s["id"]: s for s in spans}
        for s in spans:
            if "parentId" in s:
                assert by_id[s["parentId"]]["traceId"] == s["traceId"]
        traces = {s["traceId"] for s in spans}
        assert not traces & seen_traces and not ids & seen_spans
        seen_traces |= traces
        seen_spans |= ids


def test_body_equals_the_spans_the_reference_is_given():
    t = traffic(77, spans=256)
    for n in (0, 9):
        tpl = t.templates[t.template_of(n)]
        assert json.loads(t.body(n)) == tpl.spans(n)


def test_durations_keep_their_digits_and_are_drawn_anew_for_every_send():
    t = traffic(3)
    tpl = t.templates[0]
    d0, d1 = tpl.durations(0), tpl.durations(1)
    assert (d0 != d1).mean() > 0.9
    for d in (d0, d1):
        assert [len(str(x)) for x in d] == [len(str(x)) for x in tpl.duration]


def test_a_keys_durations_follow_the_pareto_law_with_no_gaps():
    """Over many sends, one key's durations are a smooth sample: the median
    is the law's, and no stretch of the middle half of the sample is empty
    (a rank sketch's error in value is unbounded across a gap)."""
    t = traffic(9, spans=1024, templates=2)
    tpl = t.templates[0]
    key = (tpl.svc[0], tpl.name[0])
    rows = [i for i in range(1024) if (tpl.svc[i], tpl.name[i]) == key]
    d = np.sort(np.concatenate([tpl.durations(n)[rows] for n in range(4000)]))
    all_d = np.concatenate([tpl.durations(n) for n in range(40)])
    assert abs(np.median(all_d) / (1000 * 2 ** (1 / 1.2) + 50) - 1) < 0.03
    assert abs(np.percentile(all_d, 99) / (1000 * 100 ** (1 / 1.2) + 50) - 1) < 0.25
    mid = d[len(d) // 4: 3 * len(d) // 4]
    assert (np.diff(mid) / mid[:-1]).max() < 0.02


def test_event_time_stays_inside_one_five_minute_bucket():
    t = traffic(1, spans=8192, templates=1)
    tpl = t.templates[0]
    last = int(tpl.timestamps(2500).max())  # more sends than a run makes
    assert gen.BASE_TS_US // 300_000_000 == last // 300_000_000


def test_a_post_size_that_does_not_divide_the_half_ring_is_refused():
    with pytest.raises(ValueError):
        traffic(1, spans=1000)


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(HERE, "..", "workloads", "*.json"))))
def test_every_workload_file_divides_the_half_ring(path):
    with open(path) as f:
        wl = json.load(f)
    assert 131072 % wl["posts"]["spans"] == 0
    assert wl["fill_spans"] % wl["posts"]["spans"] == 0
