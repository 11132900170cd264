"""The plain reference: what the server must answer, worked out on the host.

The benchmark's own copy of the exact computation the program accelerates:
Zipkin's ``DependencyLinker`` rules over reassembled trace trees, exact
quantiles, exact distinct-trace counts, name sets. It works on JSON v2 span
dicts and imports nothing of the program, and takes nothing the program made.

Spans reach the server as sends of templates (``gen.py``). Every answer here
is additive over whole traces, and a send is a set of whole traces with fresh
ids, so the reference works each template out once and weights it by how many
sends of it were acknowledged. Durations alone differ from send to send;
``Reference.durations`` takes the acknowledged sends themselves. The same functions take
an explicit list of span dicts, which is how the tests check them by hand.
"""

from __future__ import annotations

from collections import Counter, defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

Edge = Tuple[str, str]


def _svc(span: dict, side: str) -> Optional[str]:
    name = (span.get(side) or {}).get("serviceName")
    return name.lower() if name else None


def _is_error(span: dict) -> bool:
    return "error" in (span.get("tags") or {})


def link_trace(spans: Sequence[dict], calls: Counter, errors: Counter) -> None:
    """Add one whole trace's dependency links to ``calls``/``errors``.

    Rules (zipkin2 ``DependencyLinker``): a CLIENT span with children defers
    to them; no kind with both services known acts as CLIENT; SERVER/CONSUMER
    link remote -> local, a root SERVER with no remote links nothing;
    CLIENT/PRODUCER link local -> remote; messaging spans need both sides;
    for RPC spans the nearest ancestor with a kind supplies a missing parent
    (always, for SERVER), and a CLIENT whose service differs from that
    ancestor's backfills ancestor -> client with no error; an ``error`` tag
    counts an error on the span's own link.
    """
    by_id: Dict[str, dict] = {}
    for s in spans:
        # a shared SERVER half hangs below the CLIENT half of the same id
        key = s["id"] + ("/s" if s.get("shared") else "")
        by_id[key] = s
    children: Dict[int, int] = Counter()
    parent_of: Dict[int, Optional[dict]] = {}
    for s in spans:
        if s.get("shared") and s["id"] in by_id:
            parent = by_id[s["id"]]
        else:
            pid = s.get("parentId")
            parent = by_id.get(pid + "/s") or by_id.get(pid) if pid else None
        parent_of[id(s)] = parent
        if parent is not None:
            children[id(parent)] += 1

    def add(parent: str, child: str, err: bool) -> None:
        calls[(parent, child)] += 1
        if err:
            errors[(parent, child)] += 1

    for s in spans:
        kind = s.get("kind")
        local, remote = _svc(s, "localEndpoint"), _svc(s, "remoteEndpoint")
        if kind == "CLIENT" and children[id(s)]:
            continue
        if kind is None:
            if local is None or remote is None:
                continue
            kind = "CLIENT"
        if kind in ("SERVER", "CONSUMER"):
            child, parent = local, remote
            if parent_of[id(s)] is None and parent is None:
                continue
        else:
            parent, child = local, remote
        if kind in ("PRODUCER", "CONSUMER"):
            if parent is not None and child is not None:
                add(parent, child, _is_error(s))
            continue
        anc = parent_of[id(s)]
        while anc is not None and anc.get("kind") is None:
            anc = parent_of[id(anc)]
        if anc is not None:
            anc_name = _svc(anc, "localEndpoint")
            if anc_name is not None:
                if kind == "CLIENT" and local is not None and anc_name != local:
                    add(anc_name, local, False)
                if kind == "SERVER" or parent is None:
                    parent = anc_name
        if parent is None or child is None:
            continue
        add(parent, child, _is_error(s))


def links(spans: Iterable[dict]) -> Dict[Edge, Tuple[int, int]]:
    """Dependency links of a set of spans, grouped into traces by id."""
    by_trace: Dict[str, List[dict]] = defaultdict(list)
    for s in spans:
        by_trace[s["traceId"]].append(s)
    calls: Counter = Counter()
    errors: Counter = Counter()
    for trace in by_trace.values():
        link_trace(trace, calls, errors)
    return {e: (n, errors.get(e, 0)) for e, n in calls.items()}


class TemplateFacts:
    """Everything the answers need from one template, worked out once."""

    def __init__(self, template) -> None:
        spans = template.spans()
        self.n_spans = len(spans)
        self.links = links(spans)
        self.template = template
        rows_of: Dict[Tuple[str, str], List[int]] = defaultdict(list)
        traces_of: Dict[str, set] = defaultdict(set)
        self.names: Dict[str, set] = defaultdict(set)
        self.services: set = set()
        for i, s in enumerate(spans):
            local, remote = _svc(s, "localEndpoint"), _svc(s, "remoteEndpoint")
            self.services.update(x for x in (local, remote) if x)
            if local:
                traces_of[local].add(s["traceId"])
                if s.get("name"):
                    self.names[local].add(s["name"].lower())
            if s.get("duration") is not None:
                rows_of[(local, s.get("name", "").lower())].append(i)
        # per (service, span name): the rows of the body that carry it
        self.key_rows = {k: np.asarray(v) for k, v in rows_of.items()}
        self.traces_per_service = {k: len(v) for k, v in traces_of.items()}
        self.n_traces = len({s["traceId"] for s in spans})


class Reference:
    """The exact answers over ``counts[t]`` acknowledged sends of template t."""

    def __init__(self, templates) -> None:
        self.facts = [TemplateFacts(t) for t in templates]

    def total_spans(self, counts: Sequence[int]) -> int:
        return sum(c * f.n_spans for c, f in zip(counts, self.facts))

    def links(self, counts: Sequence[int]) -> Dict[Edge, Tuple[int, int]]:
        out: Dict[Edge, List[int]] = defaultdict(lambda: [0, 0])
        for c, f in zip(counts, self.facts):
            if c:
                for e, (n, err) in f.links.items():
                    out[e][0] += c * n
                    out[e][1] += c * err
        return {e: (v[0], v[1]) for e, v in out.items()}

    def services(self, counts: Sequence[int]) -> List[str]:
        return sorted(set().union(
            *(f.services for c, f in zip(counts, self.facts) if c)))

    def span_names(self, counts: Sequence[int], service: str) -> List[str]:
        return sorted(set().union(
            *(f.names.get(service, set())
              for c, f in zip(counts, self.facts) if c)))

    def cardinalities(self, counts: Sequence[int]) -> Dict[str, int]:
        """Distinct traces per local service, and ``_global``. Ids never
        repeat between sends, so distinct counts add up."""
        out: Counter = Counter()
        for c, f in zip(counts, self.facts):
            if c:
                out["_global"] += c * f.n_traces
                for svc, n in f.traces_per_service.items():
                    out[svc] += c * n
        return dict(out)

    def durations(self, acked: Sequence[Tuple[int, int]]
                  ) -> Dict[Tuple[str, str], np.ndarray]:
        """Per (service, span name): every duration sent, over the
        acknowledged sends ``(send number, template)``."""
        parts: Dict[Tuple[str, str], List[np.ndarray]] = defaultdict(list)
        for t, f in enumerate(self.facts):
            ns = [n for n, tt in acked if tt == t]
            if not ns:
                continue
            sent = np.stack([f.template.durations(n) for n in ns])
            for key, rows in f.key_rows.items():
                parts[key].append(sent[:, rows].ravel())
        return {k: np.concatenate(v) for k, v in parts.items()}


def exact_quantile(values: np.ndarray, q: float) -> float:
    """The q-quantile by linear interpolation between order statistics
    (numpy's default, ``statistics.quantiles(method="inclusive")``)."""
    return float(np.quantile(values, q))
