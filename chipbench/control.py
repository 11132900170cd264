"""The control: the reference put in the program's place, with one guarantee
the configuration states broken. It has to come out as not correct.

``answers_of`` builds what a faultless server would say after a set of
acknowledged sends. The controls break it in the ways that would tempt a
later PR: ``drop`` leaves the last acknowledged batches out of every answer
(an ack that is not durable and applied), ``sample`` works the quantile
sketches out from one span in ``sample`` (a sketch at lower precision: fewer
points kept per key), and ``card_sample`` counts distinct traces from one
trace in ``card_sample`` (a cardinality sketch fed a share of the traces).
``run.py --control`` evaluates them on a real run's own sends, at the cell's
own size, and prints the numbers beside their limits;
``tests/test_control.py`` keeps them as tests at a small size. The
benchmark's own runs do not run this.
"""

from __future__ import annotations

import numpy as np

import compare


def answers_of(ref, traffic, sends, drop: int = 0, sample: int = 1,
               card_sample: int = 1) -> dict:
    acked = [s for s in sends if s["status"] == 202]
    said = acked[:len(acked) - drop] if drop else acked
    counts = compare.counts_of(said, len(traffic.templates))
    durs = ref.durations([(s["n"], s["template"]) for s in said])
    rows = []
    for (svc, name), vals in durs.items():
        part = vals[::sample] if sample > 1 else vals
        rows.append({"serviceName": svc, "spanName": name, "count": len(vals),
                     "quantiles": {"0.5": float(np.quantile(part, 0.5)),
                                   "0.99": float(np.quantile(part, 0.99))}})
    svcs = ref.services(counts)
    return {
        "applied": ref.total_spans(counts),
        "dependencies": [
            {"parent": p, "child": c, "callCount": n, "errorCount": e}
            for (p, c), (n, e) in ref.links(counts).items()],
        "percentiles": rows,
        "cardinalities": {k: -(-n // card_sample) for k, n in
                          ref.cardinalities(counts).items()},
        "services": svcs,
        "span_names": {s: ref.span_names(counts, s) for s in svcs[:2]},
    }


CONTROLS = (("sound", {}), ("batch_dropped", {"drop": 1}),
            ("sketch_1_in_16", {"sample": 16}),
            ("card_1_in_4", {"card_sample": 4}))


def readings(ref, traffic, result: dict, guarantees: dict) -> dict:
    """-> {control name: its numbers that are over their limits, and the
    sketch numbers whatever they read} on this run's sends."""
    out = {}
    for name, kw in CONTROLS:
        final = answers_of(ref, traffic, result["sends"], **kw)
        numbers = compare.compare(ref, traffic, result, final, guarantees)
        out[name] = {"correct": compare.verdict(numbers),
                     "over": {k: v for k, v in numbers.items() if v[0] > v[1]},
                     "sketches": {k: numbers[k][0] for k in (
                         "p50_rel_err_max", "p99_rank_err_max",
                         "card_global_rel_err", "card_service_rel_err_max")}}
    return out
