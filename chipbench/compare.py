"""What decides ``correct``: the server's answers against the reference.

Each comparison yields named numbers, each beside its limit; a run is correct
when every number is within its limit. Exact answers (span counts, name sets,
dependency links) have the limit 0. The sketches' limits are the ones the
configuration states under ``guarantees`` (they are the product's own,
``PERF.md`` section 2), each held for every key or service the guarantee
speaks of. The numbers and limits are printed in every run, on standard error
and in the result line.

``final`` is what the server said over its sockets after the drain, fetched
by ``run.py``: nothing here reads program state.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

import reference as ref_mod


def _rel(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-12)


def _link_map(links) -> Dict[Tuple[str, str], Tuple[int, int]]:
    return {(l["parent"], l["child"]):
            (int(l.get("callCount", 0)), int(l.get("errorCount", 0)))
            for l in links}


def counts_of(sends: Sequence[dict], n_templates: int) -> List[int]:
    out = [0] * n_templates
    for s in sends:
        if s["status"] == 202:
            out[s["template"]] += 1
    return out


def compare(ref: ref_mod.Reference, traffic, result: dict, final: dict,
            guarantees: dict, detail: dict = None) -> Dict[str, List[float]]:
    """-> {name: [value, limit]} for every number compared. ``detail``, where
    given, receives what helps to find a number's cause (the worst keys)."""
    detail = {} if detail is None else detail
    g = guarantees
    sends = result["sends"]
    counts = counts_of(sends, len(traffic.templates))
    sent_spans = ref.total_spans(counts)
    out: Dict[str, List[float]] = {}

    # durability and the boundary: every batch that got its 202 is applied
    # after the drain, and the server counts exactly the spans sent
    out["spans_applied_diff"] = [abs(final["applied"] - sent_spans), 0]
    out["batches_not_202"] = [
        sum(1 for s in sends if s["status"] != 202), 0]

    # parse: services and span names as sent
    want_svcs = ref.services(counts)
    out["services_diff"] = [
        len(set(final["services"]) ^ set(want_svcs)), 0]
    out["span_names_diff"] = [
        sum(len(set(names) ^ set(ref.span_names(counts, svc)))
            for svc, names in final["span_names"].items()), 0]

    # device step, maintenance and the linker: links edge for edge, after the
    # ring has been written over many times
    want_links = ref.links(counts)
    got_links = _link_map(final["dependencies"])
    out["links_wrong_edges"] = [
        sum(1 for e in set(want_links) | set(got_links)
            if want_links.get(e) != got_links.get(e)), 0]
    out["links_calls_diff"] = [
        abs(sum(v[0] for v in got_links.values())
            - sum(v[0] for v in want_links.values())), 0]

    # sketches: counts exact, quantiles and cardinalities within the
    # configuration's stated error, for every key and every service
    durs = ref.durations(
        [(s["n"], s["template"]) for s in sends if s["status"] == 202])
    rows = {(r["serviceName"], r["spanName"]): r for r in final["percentiles"]}
    out["percentile_keys_diff"] = [len(set(rows) ^ set(durs)), 0]
    count_diff = 0
    p50_errs, p99_errs = [], []
    for key, vals in durs.items():
        r = rows.get(key)
        if r is None:
            continue
        n = len(vals)
        if r["count"] != n:
            count_diff += 1
            continue
        if n >= g["p50_min_n"]:
            want = ref_mod.exact_quantile(vals, 0.5)
            p50_errs.append((_rel(r["quantiles"]["0.5"], want), key,
                             r["quantiles"]["0.5"], want, n))
        if n >= g["p99_min_n"]:
            # the tail is held in rank, the sketch's own measure: where in
            # the key's exact sample the answer lies. In value a Pareto
            # tail's 99th percentile of a few hundred durations is loose by
            # the gap between two of its largest, whatever the sketch
            got = r["quantiles"]["0.99"]
            rank = float(np.searchsorted(np.sort(vals), got, "right")) / n
            p99_errs.append((abs(rank - 0.99), key, got, n, _rel(
                got, ref_mod.exact_quantile(vals, 0.99))))
    p50_errs.sort(reverse=True)
    p99_errs.sort(reverse=True)
    detail["p99_worst"] = p99_errs[:5]
    detail["p99_rel_err_max"] = max((e[4] for e in p99_errs), default=None)
    detail["p50_worst"] = p50_errs[:5]
    detail["p50_keys_checked"] = len(p50_errs)
    detail["p50_rel_err_keys_p99"] = float(
        np.percentile([e[0] for e in p50_errs], 99)) if p50_errs else None
    out["percentile_counts_diff"] = [count_diff, 0]
    out["percentile_rows_unchecked"] = [0 if p50_errs else 1, 0]
    out["p50_rel_err_max"] = [
        p50_errs[0][0] if p50_errs else 1.0, g["p50_rtol"]]
    out["p99_rank_err_max"] = [
        p99_errs[0][0] if p99_errs else 1.0, g["p99_rank_tol"]]
    cards = ref.cardinalities(counts)
    got_cards = final["cardinalities"]
    out["card_global_rel_err"] = [
        _rel(got_cards.get("_global", 0), cards["_global"]), g["card_global_rtol"]]
    out["card_service_rel_err_max"] = [
        max((_rel(got_cards.get(svc, 0), n) for svc, n in cards.items()
             if svc != "_global" and n >= g["card_service_min_n"]),
            default=1.0), g["card_service_rtol"]]
    return out


def verdict(numbers: Dict[str, List[float]]) -> bool:
    return all(v <= limit for v, limit in numbers.values())
