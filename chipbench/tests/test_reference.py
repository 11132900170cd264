"""The reference against a ten-span case worked by hand."""

import reference


def ep(name):
    return {"serviceName": name}


def span(trace, sid, parent=None, kind="CLIENT", local=None, remote=None,
         error=False, shared=False, duration=None, name="op"):
    s = {"traceId": trace, "id": sid, "name": name}
    if kind:
        s["kind"] = kind
    if parent:
        s["parentId"] = parent
    if local:
        s["localEndpoint"] = ep(local)
    if remote:
        s["remoteEndpoint"] = ep(remote)
    if error:
        s["tags"] = {"error": "boom"}
    if shared:
        s["shared"] = True
    if duration is not None:
        s["duration"] = duration
    return s


TEN = [
    # A: a chain of three clients; only the leaf links, and its RPC ancestor
    # (service b) differs from its own service (c): b->c is backfilled
    span("A", "1", local="a", remote="b"),
    span("A", "2", parent="1", local="b", remote="c"),
    span("A", "3", parent="2", local="c", remote="d", error=True),
    # B: a lone client
    span("B", "1", local="a", remote="b"),
    # C: a root server with no known caller links nothing; its client child
    # is of the same service, so no backfill
    span("C", "1", kind="SERVER", local="b"),
    span("C", "2", parent="1", local="b", remote="c"),
    # D: client and shared server half of one id: the server half reports,
    # with the client's service as the parent
    span("D", "1", local="a", remote="b"),
    span("D", "1", kind="SERVER", local="b", shared=True),
    # E: messaging needs both sides
    span("E", "1", kind="PRODUCER", local="a", remote="kafka"),
    span("E", "2", parent="1", kind="CONSUMER", local="c", remote="kafka"),
]


def test_ten_spans_by_hand():
    assert len(TEN) == 10
    assert reference.links(TEN) == {
        ("a", "b"): (2, 0),
        ("b", "c"): (2, 0),
        ("c", "d"): (1, 1),
        ("a", "kafka"): (1, 0),
        ("kafka", "c"): (1, 0),
    }


def test_a_trace_cut_in_two_links_differently():
    """Why POSTs hold whole traces: the halves of trace A alone say else."""
    whole = reference.links(TEN[:3])
    halves = reference.links(TEN[:2])
    for k, v in reference.links(TEN[2:3]).items():
        halves[k] = tuple(a + b for a, b in zip(halves.get(k, (0, 0)), v))
    assert whole != halves


def test_exact_quantile_is_numpys_linear_interpolation():
    import numpy as np
    assert reference.exact_quantile(np.array([1, 2, 3, 4]), 0.5) == 2.5
    assert reference.exact_quantile(np.array([10, 20, 30]), 0.99) == 29.8
