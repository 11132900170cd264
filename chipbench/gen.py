"""Seeded span traffic: whole traces, fixed-size JSON v2 POST bodies.

One general generator, driven by the ``fleet`` block of a configuration file
and the ``posts`` block of a workload file. The shapes are those of
``tests/fixtures.py:lots_of_spans`` (client spans in call chains of depth 1-4
across a service mesh, Pareto durations, 2% errors), with one difference that
matters: **no trace crosses a POST**. Every body holds exactly ``post_spans``
spans, the last trace shortened to fit.

Bodies are made once per run as ``templates`` (K bodies from the seed) and
re-stamped for every send: fresh 64-bit trace and span ids (a bijective mix
of a global counter, so no id repeats in a run), event timestamps that
advance with the send number, and a duration drawn anew from the fleet's
Pareto law for every span of every send (within the decade of the template's
own draw, so that the digit count and with it every byte position stays), so
that a key's durations are a dense sample of a smooth law, as ``lots_of_spans``
gives them, and not a few dozen values or bumps with gaps between them: a
rank sketch's error in value is unbounded across a gap. The stamping is a few numpy writes into fixed
byte positions, so one client process can offer hundreds of thousands of
spans a second without the generator being the bottleneck. What a template's
spans say besides ids, timestamps and durations (services, names, errors,
tree shape) is the same in every send, which is what lets the reference
(``reference.py``) count answers per template and weight them by sends.

Nothing here imports the program or JAX.
"""

from __future__ import annotations

import random
from typing import Dict, List, Optional

import numpy as np

# Event-time base: 2026-07-29 00:01:00 UTC in microseconds. One minute into
# an hour so that a run's few minutes of event time stay inside one 5-minute
# time-tier bucket and one 60-minute link bucket (see the configuration's
# ``assumed``: windowed reads over sealed buckets are a later cell).
BASE_TS_US = 1_785_283_200_000_000 + 60_000_000
TRACE_GAP_US = 10  # event time between consecutive traces of a body



def query_window(lookback_ms: int) -> dict:
    """Explicit endTs/lookback (ms) that cover a run's event time: the base
    minute plus four."""
    return {"endTs": BASE_TS_US // 1000 + 240_000, "lookback": lookback_ms}


_HEX = np.frombuffer(b"0123456789abcdef", dtype=np.uint8)
_SHIFTS = np.arange(60, -4, -4, dtype=np.uint64)
_POW10 = (10 ** np.arange(15, -1, -1)).astype(np.uint64)
_M64 = (1 << 64) - 1


def mix64(v: np.ndarray) -> np.ndarray:
    """splitmix64's finaliser: a bijection on u64, so distinct counters give
    distinct ids that look random to the server's hashes."""
    v = v.astype(np.uint64)
    with np.errstate(over="ignore"):
        v = (v + np.uint64(0x9E3779B97F4A7C15))
        v = (v ^ (v >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        v = (v ^ (v >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        v = v ^ (v >> np.uint64(31))
    return v


def _hex16(v: np.ndarray) -> np.ndarray:
    return _HEX[((v[:, None] >> _SHIFTS) & np.uint64(15)).astype(np.intp)]


def _dec16(v: np.ndarray) -> np.ndarray:
    return ((v[:, None] // _POW10) % np.uint64(10)).astype(np.uint8) + 48


class Template:
    """One POST body's worth of whole traces, ready to be re-stamped."""

    def __init__(self, rng: random.Random, fleet: dict, post_spans: int,
                 id_salt: int) -> None:
        services = [f"svc{i:02d}" for i in range(fleet["services"])]
        names = [f"op{i:03d}" for i in range(fleet["span_names"])]
        alpha = fleet["duration_pareto_alpha"]
        err_p = fleet["error_share"]
        lo_d, hi_d = fleet["trace_depth"]
        self.post_spans = post_spans
        self.id_salt = id_salt
        self._alpha = float(alpha)
        rows = []  # (trace index, level, svc, rsvc, name, duration, error, ts offset)
        tix = 0
        while len(rows) < post_spans:
            depth = min(rng.randint(lo_d, hi_d), post_spans - len(rows))
            caller = rng.randrange(len(services))
            ts = tix * TRACE_GAP_US
            for level in range(depth):
                callee = rng.randrange(len(services))
                dur = int(rng.paretovariate(alpha) * 1000) + 50
                err = rng.random() < err_p
                rows.append((tix, level, caller, callee,
                             rng.randrange(len(names)), dur, err, ts))
                caller = callee
                ts += rng.randint(1, 4)
            tix += 1
        self.n_traces = tix
        cols = list(zip(*rows))
        self.trace = np.array(cols[0], dtype=np.int64)
        self.level = np.array(cols[1], dtype=np.int64)
        self.svc = [services[i] for i in cols[2]]
        self.rsvc = [services[i] for i in cols[3]]
        self.name = [names[i] for i in cols[4]]
        self.duration = np.array(cols[5], dtype=np.int64)
        self.error = np.array(cols[6], dtype=bool)
        self.ts_off = np.array(cols[7], dtype=np.uint64)
        # event time one send covers, the same for every template
        self.event_us = post_spans * TRACE_GAP_US
        self._build_body()

    def _build_body(self) -> None:
        zeros = "0" * 16
        parts: List[str] = []
        pos = 1  # after '['
        p_tid, p_id, p_pid, p_ts, p_dur = [], [], [], [], []
        for i in range(self.post_spans):
            head = '{"traceId":"'
            p_tid.append(pos + len(head))
            s = head + zeros + '",'
            if self.level[i] > 0:
                p_pid.append(pos + len(s) + len('"parentId":"'))
                s += '"parentId":"' + zeros + '",'
            p_id.append(pos + len(s) + len('"id":"'))
            s += '"id":"' + zeros + '","kind":"CLIENT","name":"' + self.name[i]
            s += '","timestamp":'
            p_ts.append(pos + len(s))
            s += zeros + ',"duration":'
            p_dur.append(pos + len(s))
            s += str(int(self.duration[i]))
            s += (',"localEndpoint":{"serviceName":"' + self.svc[i]
                  + '"},"remoteEndpoint":{"serviceName":"' + self.rsvc[i] + '"}')
            if self.error[i]:
                s += ',"tags":{"error":"boom"}'
            s += "}"
            parts.append(s)
            pos += len(s) + 1  # the comma (or the closing bracket)
        text = "[" + ",".join(parts) + "]"
        self.buf = np.frombuffer(text.encode("ascii"), dtype=np.uint8).copy()
        k = np.arange(16)
        self._ix_tid = (np.array(p_tid)[:, None] + k).ravel()
        self._ix_id = (np.array(p_id)[:, None] + k).ravel()
        self._ix_pid = (np.array(p_pid, dtype=np.int64)[:, None] + k).ravel()
        self._ix_ts = (np.array(p_ts)[:, None] + k).ravel()
        self._has_parent = self.level > 0
        # durations keep their digit count, so their bytes stay in place:
        # one group of positions per digit count
        digits = np.array([len(str(int(d))) for d in self.duration])
        p_dur = np.array(p_dur)
        self._dur_groups = []
        for d in np.unique(digits):
            rows = np.nonzero(digits == d)[0]
            ix = (p_dur[rows][:, None] + np.arange(d)).ravel()
            self._dur_groups.append((int(d), rows, ix))
        # a send draws each duration from the Pareto law cut to the decade
        # of the template's draw: int(x * 1000) + 50 with x in [a, b)
        self._dur_lo = np.maximum(10 ** (digits - 1), 1050).astype(np.int64)
        self._dur_hi = (10 ** digits - 1).astype(np.int64)
        alpha = self._alpha
        cdf_a = 1.0 - ((self._dur_lo - 50) / 1000.0) ** -alpha
        cdf_b = 1.0 - ((self._dur_hi + 1 - 50) / 1000.0) ** -alpha
        self._dur_cdf = (cdf_a, cdf_b - cdf_a)

    # ids and timestamps of send number ``n`` -------------------------------

    def ids(self, n: int):
        """(trace ids per span, span ids per span) of send ``n``, as u64."""
        base = np.uint64(((n * 1_000_003 + self.id_salt) << 20) & _M64)
        tid = mix64(base + self.trace.astype(np.uint64))
        sid = mix64(~(base + np.arange(self.post_spans, dtype=np.uint64)))
        return tid, sid

    def durations(self, n: int) -> np.ndarray:
        """Durations of send ``n``: a seeded draw from the Pareto law for
        every span, inside the decade of the template's own."""
        h = mix64(np.uint64(((n * 7_368_787 + self.id_salt) << 20) & _M64)
                  + np.arange(self.post_spans, dtype=np.uint64))
        u = (h >> np.uint64(11)).astype(np.float64) / float(1 << 53)
        base, width = self._dur_cdf
        x = (1.0 - (base + u * width)) ** (-1.0 / self._alpha)
        return np.clip((x * 1000.0).astype(np.int64) + 50,
                       self._dur_lo, self._dur_hi)

    def timestamps(self, n: int) -> np.ndarray:
        return np.uint64(BASE_TS_US + n * self.event_us) + self.ts_off

    def body(self, n: int, scratch: Optional[np.ndarray] = None) -> bytes:
        """The POST body of send ``n``. ``scratch`` is the caller's private
        copy of ``buf`` (one per sending thread)."""
        buf = self.buf.copy() if scratch is None else scratch
        tid, sid = self.ids(n)
        buf[self._ix_tid] = _hex16(tid).ravel()
        buf[self._ix_id] = _hex16(sid).ravel()
        # a child's parent is the previous row of its trace
        parent = np.roll(sid, 1)[self._has_parent]
        buf[self._ix_pid] = _hex16(parent).ravel()
        buf[self._ix_ts] = _dec16(self.timestamps(n)).ravel()
        dur = self.durations(n).astype(np.uint64)
        for d, rows, ix in self._dur_groups:
            buf[ix] = _dec16(dur[rows])[:, 16 - d:].ravel()
        return buf.tobytes()

    # plain spans, for the reference ---------------------------------------

    def spans(self, n: Optional[int] = None) -> List[dict]:
        """The body's spans as JSON v2 dicts. With ``n``: the ids, timestamps
        and durations of that send; without: position-made ids (the
        reference needs only the tree shape)."""
        if n is None:
            tid = self.trace.astype(np.uint64) + np.uint64(1)
            sid = np.arange(1, self.post_spans + 1, dtype=np.uint64)
            ts = np.uint64(BASE_TS_US) + self.ts_off
            dur = self.duration
        else:
            tid, sid = self.ids(n)
            ts = self.timestamps(n)
            dur = self.durations(n)
        rows = range(self.post_spans)
        out = []
        for i in rows:
            d = {
                "traceId": f"{int(tid[i]):016x}", "id": f"{int(sid[i]):016x}",
                "kind": "CLIENT", "name": self.name[i],
                "timestamp": int(ts[i]), "duration": int(dur[i]),
                "localEndpoint": {"serviceName": self.svc[i]},
                "remoteEndpoint": {"serviceName": self.rsvc[i]},
            }
            if self.level[i] > 0:
                d["parentId"] = f"{int(sid[i - 1]):016x}"
            if self.error[i]:
                d["tags"] = {"error": "boom"}
            out.append(d)
        return out


class Traffic:
    """The K templates of one run and which template send ``n`` uses."""

    def __init__(self, seed: int, fleet: dict, posts: dict) -> None:
        self.post_spans = int(posts["spans"])
        if (1 << 17) % self.post_spans:
            raise ValueError(
                f"POST size {self.post_spans} does not divide 131072: a trace "
                "could be cut by the edge of the rolled half-ring")
        k = int(posts["templates"])
        rng = random.Random(seed)
        self.templates = [
            Template(rng, fleet, self.post_spans, id_salt=seed & 0xFFFFFFFF)
            for _ in range(k)
        ]
        # every seed sends the same shapes, in another order
        self.order = list(range(k))
        rng.shuffle(self.order)

    def template_of(self, n: int) -> int:
        return self.order[n % len(self.order)]

    def body(self, n: int, scratch: Optional[Dict[int, np.ndarray]] = None
             ) -> bytes:
        t = self.template_of(n)
        tpl = self.templates[t]
        buf = None
        if scratch is not None:
            buf = scratch.get(t)
            if buf is None:
                buf = scratch[t] = tpl.buf.copy()
        return tpl.body(n, buf)
