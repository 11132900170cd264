"""The comparison that decides ``correct``, shown to fail.

The reference is put in the program's place (its exact answers are the
"server's"), which must come out correct; then one guarantee the
configuration states is broken at a time, and each must come out not
correct: an acknowledged batch missing from the answers, an answer altered
where it is produced, the quantile sketches computed at a lower precision
(from one span in sixteen), distinct traces counted from one trace in four.
"""

import json
import os

import pytest

import compare
import gen
import reference
from control import answers_of, readings

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "..", "configs", "otelcol-inproc-1chip.json")) as f:
    CONFIG = json.load(f)
G = CONFIG["guarantees"]


@pytest.fixture(scope="module")
def world():
    traffic = gen.Traffic(2_147_483_659, CONFIG["fleet"],
                          {"spans": 1024, "templates": 4})
    ref = reference.Reference(traffic.templates)
    sends = [{"n": n, "template": traffic.template_of(n), "status": 202,
              "due": float(n), "phase": "window"} for n in range(120)]
    result = {"sends": sends}
    return traffic, ref, sends, result


def numbers(world, final):
    traffic, ref, _, result = world
    return compare.compare(ref, traffic, result, final, G)


def test_the_reference_in_the_programs_place_is_correct(world):
    traffic, ref, sends, _ = world
    got = numbers(world, answers_of(ref, traffic, sends))
    assert compare.verdict(got), got


def test_control_an_acknowledged_batch_missing_is_not_correct(world):
    traffic, ref, sends, _ = world
    got = numbers(world, answers_of(ref, traffic, sends, drop=1))
    assert not compare.verdict(got)
    assert got["spans_applied_diff"][0] == 1024
    assert got["links_wrong_edges"][0] > 0
    assert got["percentile_counts_diff"][0] > 0


def test_an_answer_altered_where_it_is_produced_is_not_correct(world):
    traffic, ref, sends, _ = world
    final = answers_of(ref, traffic, sends)
    final["dependencies"][0]["callCount"] += 1
    got = numbers(world, final)
    assert not compare.verdict(got)
    assert got["links_wrong_edges"][0] == 1


def test_control_sketches_at_a_lower_precision_are_not_correct(world):
    traffic, ref, sends, _ = world
    got = numbers(world, answers_of(ref, traffic, sends, sample=16))
    assert not compare.verdict(got)
    assert got["p50_rel_err_max"][0] > 3 * G["p50_rtol"]
    assert got["p99_rank_err_max"][0] > 3 * G["p99_rank_tol"]


def test_control_distinct_traces_from_one_in_four_are_not_correct(world):
    traffic, ref, sends, _ = world
    got = numbers(world, answers_of(ref, traffic, sends, card_sample=4))
    assert not compare.verdict(got)
    assert got["card_global_rel_err"][0] > 3 * G["card_global_rtol"]
    assert got["card_service_rel_err_max"][0] > 3 * G["card_service_rtol"]
    assert [k for k, v in got.items() if v[0] > v[1]] == [
        "card_global_rel_err", "card_service_rel_err_max"]


def test_one_key_of_all_beyond_its_bound_is_not_correct(world):
    """The median's bound holds for every key, not for most."""
    traffic, ref, sends, _ = world
    final = answers_of(ref, traffic, sends)
    row = max(final["percentiles"], key=lambda r: r["count"])
    assert row["count"] >= G["p50_min_n"]
    row["quantiles"]["0.5"] *= 1.2
    got = numbers(world, final)
    assert not compare.verdict(got)
    assert [k for k, v in got.items() if v[0] > v[1]] == ["p50_rel_err_max"]


def test_the_controls_as_run_py_reads_them(world):
    traffic, ref, _, result = world
    got = readings(ref, traffic, result, G)
    assert got["sound"]["correct"] and not got["sound"]["over"]
    assert not got["batch_dropped"]["correct"]
    assert not got["sketch_1_in_16"]["correct"]
    assert not got["card_1_in_4"]["correct"]
    assert got["card_1_in_4"]["sketches"]["card_global_rel_err"] > 0.7
