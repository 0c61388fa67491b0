"""The two per-layer readers PR 28 added, on recorded input: the traced
chip run of ``deep-pq-cosine.c32`` (programs, store, bucket counts) for
``pq_scan_roofline_pct``, and a page of ``/v1/metrics`` from a CPU drive
for ``pq_compress_s``. Where the program has no such series or store, as
the parent has not, each reads None and raises nothing."""

import copy
import json
import os

import pytest

import kernel_costs
import kernel_costs_pq
import run
import wire

HERE = os.path.dirname(os.path.abspath(__file__))
BUCKETS = "weaviate_tpu_query_batcher_compile_bucket_total"


def buckets_page(counts: dict) -> wire.Prom:
    return wire.Prom("\n".join(f'{BUCKETS}{{b="{b}",k="16"}} {n}'
                               for b, n in counts.items()))


@pytest.fixture
def ctx():
    with open(os.path.join(HERE, "recorded",
                           "v5e_deep-pq-cosine_c32_traced.json")) as f:
        rec = json.load(f)
    with open(os.path.join(run.HERE, "configs",
                           "deep-pq-cosine.json")) as f:
        config = json.load(f)
    return {"trace": {"programs": rec["programs"]},
            "trace_marks": {"before": buckets_page(rec["buckets_before"]),
                            "after": buckets_page(rec["buckets_after"])},
            "store": rec["store"], "device": rec["device"],
            "config": config, "mix": {"filter": None}, "k": config["k"],
            "reported": rec["reported_pct"]}


@pytest.mark.parametrize("name,source,layer,moves", [
    ("pq_scan_roofline_pct", "device_trace", "kernels", "qps"),
    ("pq_compress_s", "program_span", "set-up, off the request path",
     "setup_s")])
def test_both_metrics_are_declared_for_the_pq_cell_only(name, source, layer,
                                                        moves):
    with open(os.path.join(run.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = {m["name"]: m for m in bench["per_layer"]}[name]
    assert (m["source"], m["layer"], m["moves"]) == (source, layer, moves)
    assert m["workloads"] == ["deep-pq-cosine.c32"]
    assert os.path.exists(os.path.join(run.HERE, "layer_metrics",
                                       name + ".py"))
    # the accepted roofline and rescore metrics keep their lists
    for kept in ("scan_roofline_pct", "rescore_ms"):
        assert "deep-pq-cosine.c32" not in {
            m["name"]: m for m in bench["per_layer"]}[kept]["workloads"]


def test_the_scan_cost_is_the_algorithms(ctx):
    rows, m, dim, cents, k = 262144, 96, 96, 256, 10
    for b in (1, 32):
        cost = kernel_costs_pq.scan_cost(ctx["store"], dim, cents, b, k)
        assert cost["flops"] == 2.0 * b * dim * cents + b * rows * m
        assert cost["int_ops"] == 0.0
        assert cost["bytes"] == (rows * m + b * m * cents * 4 + b * dim * 4
                                 + b * 16 * k * 8)
    # bytes-bound at every batch the cell dispatches: the codes, read once
    peak = kernel_costs.peaks("TPU v5 lite")
    seconds, by = kernel_costs.least_seconds(cost, peak)
    assert by == "bytes" and 30e-6 < seconds < 36e-6


def test_the_roofline_share_of_the_recorded_run(ctx):
    share = run.read_layer_metric("pq_scan_roofline_pct", ctx)
    assert share == pytest.approx(ctx["reported"], rel=1e-9)
    assert 0.0 < share < 100.0


def test_a_share_over_100_fails_the_run(ctx):
    ctx["trace"]["programs"]["jit_pq_topk"] = [1e-6, 8]
    with pytest.raises(RuntimeError, match="over 100"):
        run.read_layer_metric("pq_scan_roofline_pct", ctx)


@pytest.mark.parametrize("case", ["no-trace", "uncompressed", "bq-words",
                                  "filtered", "no-scan-program",
                                  "nothing-dispatched"])
def test_the_roofline_reader_reads_none_where_there_is_nothing(ctx, case):
    ctx = dict(ctx, store=copy.deepcopy(ctx["store"]))
    if case == "no-trace":
        ctx["trace"] = None
    elif case == "uncompressed":     # a class still under its trainingLimit
        ctx["store"]["arrays"] = {"vectors": {"shape": [131072, 96],
                                              "dtype": "float32"}}
    elif case == "bq-words":
        ctx["store"]["arrays"]["codes"] = {"shape": [131072, 24],
                                           "dtype": "uint32"}
    elif case == "filtered":
        ctx["mix"] = {"filter": {"property": "bucket"}}
    elif case == "no-scan-program":
        ctx["trace"] = {"programs": {"jit_chunked_topk_distances": [1.0, 4]}}
    else:
        ctx["trace_marks"] = dict(ctx["trace_marks"],
                                  after=ctx["trace_marks"]["before"])
    assert run.read_layer_metric("pq_scan_roofline_pct", ctx) is None


def test_compress_seconds_are_read_from_the_page_at_the_windows_start():
    with open(os.path.join(HERE, "recorded",
                           "scrape_pq_window_start.prom")) as f:
        page = wire.Prom(f.read())
    got = run.read_layer_metric("pq_compress_s",
                                {"before": page, "after": wire.Prom("")})
    assert got == pytest.approx(0.1520892240005196 + 3.994199869339354e-05
                                + 1.540257644999656)


def test_on_a_parent_without_the_series_compress_seconds_read_none():
    with open(os.path.join(HERE, "recorded",
                           "scrape_filtered_before.prom")) as f:
        parent = wire.Prom(f.read())
    assert run.read_layer_metric("pq_compress_s",
                                 {"before": parent, "after": parent}) is None
