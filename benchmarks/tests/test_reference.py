"""The comparison that decides ``correct``: it passes the exact answers and
fails the faults it is there to catch (CPU, small sizes)."""

import numpy as np
import pytest

import reference
from datagen import clustered

K = 10
LIMITS = {"distance_error_max": 1e-4, "distance_scale_floor": 1.0,
          "recall_at_k_min": 0.99}
FILTER = {"property": "bucket", "operator": "less_than",
          "values": [1, 10, 50, 99]}


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(11)
    corpus, props, queries = clustered.generate(
        rng, 16384, 128, {"members": 8, "spread": 0.35, "queries": 256,
                          "int_props": {"bucket": [0, 100]}})
    return corpus, props, queries


def answers(data, flt, precision="float32"):
    corpus, props, queries = data
    pairs = [(i, FILTER["values"][i % 4] if flt else -1)
             for i in range(len(queries))]
    return reference.lower_precision(queries, corpus, props, "l2-squared", K,
                                     flt, pairs, precision)


def verdict(data, replies, flt):
    corpus, props, queries = data
    return reference.judge(replies, queries, corpus, props, "l2-squared", K,
                           flt, LIMITS)


@pytest.mark.parametrize("flt", [None, FILTER], ids=["plain", "filtered"])
def test_exact_answers_pass(data, flt):
    v = verdict(data, answers(data, flt), flt)
    assert v["correct"], v["numbers"]
    assert v["recall_at_k"] == 1.0


def test_one_id_swapped_for_a_non_neighbour_fails(data):
    replies = answers(data, None)
    far = int(np.argmax(((data[0] - data[2][5]) ** 2).sum(-1)))
    replies["ids"][5, 3] = far
    v = verdict(data, replies, None)
    assert not v["correct"]
    assert not v["numbers"]["distance_error_max"]["ok"]


def test_one_distance_of_another_id_fails(data):
    replies = answers(data, None)
    replies["dists"][7, [0, 9]] = replies["dists"][7, [9, 0]]
    v = verdict(data, replies, None)
    assert not v["numbers"]["distance_error_max"]["ok"]


def test_wrong_neighbours_with_their_own_distances_fail_on_recall(data):
    corpus, _, queries = data
    replies = answers(data, None)
    q = reference.prepare(queries, "l2-squared")
    # every reply's ranks 11..20, with the exact distances of those rows
    d = ((q[:, None, :] - corpus[None, :2048, :]) ** 2).sum(-1)
    order = np.argsort(d, axis=1)[:, K:2 * K]
    replies["ids"] = order
    replies["dists"] = np.take_along_axis(d, order, axis=1).astype(np.float64)
    v = verdict(data, replies, None)
    assert v["numbers"]["distance_error_max"]["ok"]
    assert not v["numbers"]["recall_at_k"]["ok"]


@pytest.mark.parametrize("fault", ["short", "repeated", "outside_filter",
                                   "failed"])
def test_malformed_replies_fail(data, fault):
    replies = answers(data, FILTER)
    if fault == "short":
        replies["n_results"][3] = K - 1
    elif fault == "repeated":
        replies["ids"][3, 1] = replies["ids"][3, 0]
    elif fault == "outside_filter":
        bound = replies["bound"][3]
        replies["ids"][3, 0] = int(np.flatnonzero(
            data[1]["bucket"] >= bound)[0])
    else:
        replies["failed"][3] = True
    v = verdict(data, replies, FILTER)
    assert v["numbers"]["malformed_replies"]["value"] == 1
    assert not v["correct"]


def test_readback_catches_a_changed_vector_and_property(data):
    corpus, props, _ = data
    rows = [3, 9]
    objs = [{"vector": corpus[i].tolist(),
             "properties": {"bucket": int(props["bucket"][i])}} for i in rows]
    assert reference.judge_readback(objs, rows, corpus, props)["ok"]
    objs[0]["vector"][0] += 1e-3
    assert reference.judge_readback(objs, rows, corpus, props)["value"] == 1
    objs[1]["properties"]["bucket"] += 1
    assert reference.judge_readback(objs, rows, corpus, props)["value"] == 2
    assert reference.judge_readback([None, None], rows, corpus,
                                    props)["value"] == 2


def test_bfloat16_rounding_is_to_nearest_even():
    x = np.array([1.0, 1.00390625, 1.005859375, -3.14159], np.float32)
    got = reference.to_bfloat16(x)
    assert got[0] == 1.0
    assert got[1] == 1.0            # exactly half-way: to even
    assert got[2] == 1.0078125
    assert abs(got[3] - x[3]) <= 2 ** -7
