"""The two per-layer readers PR 32 added, on recorded input: the traced
chip run of ``gist-sq-l2.c32`` (programs, store, bucket counts) for
``sq_scan_roofline_pct``, and a page of ``/v1/metrics`` from a CPU drive
for ``sq_compress_s``. Where the program has no such series or store, as
the parent has not (it drops ``sq`` and serves float32 rows), each reads
None and raises nothing."""

import copy
import json
import os

import pytest

import kernel_costs
import kernel_costs_sq
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
                           "v5e_gist-sq-l2_c32_traced.json")) as f:
        rec = json.load(f)
    with open(os.path.join(run.HERE, "configs", "gist-sq-l2.json")) as f:
        config = json.load(f)
    return {"trace": {"programs": rec["programs"]},
            "trace_marks": {"before": buckets_page(rec["buckets_before"]),
                            "after": buckets_page(rec["buckets_after"])},
            "store": rec["store"], "device": rec["device"],
            "config": config, "mix": {"filter": None}, "k": config["k"],
            "reported": rec["reported_pct"]}


@pytest.mark.parametrize("name,source,layer,moves", [
    ("sq_scan_roofline_pct", "device_trace", "kernels", "qps"),
    ("sq_compress_s", "program_span", "set-up, off the request path",
     "setup_s")])
def test_both_metrics_are_declared_for_the_sq_cell_only(name, source, layer,
                                                        moves):
    with open(os.path.join(run.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = {m["name"]: m for m in bench["per_layer"]}[name]
    assert (m["source"], m["layer"], m["moves"]) == (source, layer, moves)
    assert m["workloads"] == ["gist-sq-l2.c32"]
    assert os.path.exists(os.path.join(run.HERE, "layer_metrics",
                                       name + ".py"))
    # the accepted roofline, rescore and pq metrics keep their lists
    for kept in ("scan_roofline_pct", "rescore_ms", "pq_scan_roofline_pct",
                 "pq_compress_s"):
        assert "gist-sq-l2.c32" not in {
            m["name"]: m for m in bench["per_layer"]}[kept]["workloads"]


def test_the_cell_and_its_configuration_are_declared():
    with open(os.path.join(run.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, config, mix = run.find_cell(bench, "gist-sq-l2.c32")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gist-sq-l2", "nearvector-c32", 1)
    assert len(cell["why"]) <= 200 and mix["filter"] is None
    entry = {c["name"]: c for c in bench["configs"]}["gist-sq-l2"]
    assert entry["reduced"] == ["rows"] == sorted(config["reduced"])
    assert (config["dim"], config["metric"], config["k"]) == (
        960, "l2-squared", 10)
    assert config["class"]["vectorIndexConfig"]["sq"] == {
        "enabled": True, "trainingLimit": 100000}
    assert config["scan_programs"] == ["^jit_sq_topk$"]
    assert run.metrics_of(bench, "per_layer", "gist-sq-l2.c32")[-2:] == [
        m for m in bench["per_layer"] if m["name"].startswith("sq_")]


def test_the_scan_cost_is_the_algorithms(ctx):
    rows, dim, k = 262144, 960, 10
    for b in (1, 32):
        cost = kernel_costs_sq.scan_cost(ctx["store"], b, k)
        assert cost["flops"] == 0.0
        assert cost["int_ops"] == 2.0 * b * rows * dim
        assert cost["bytes"] == (rows * dim + 4 * rows + b * dim * 4
                                 + b * 16 * k * 8)
    # b = 32: 16.1 GOP (41 us at the int8 peak) against 253 MB: bytes-bound
    assert cost["int_ops"] == pytest.approx(16.1e9, rel=0.01)
    peak = kernel_costs.peaks("TPU v5 lite")
    seconds, by = kernel_costs.least_seconds(cost, peak)
    assert by == "bytes" and 0.30e-3 < seconds < 0.32e-3
    assert cost["int_ops"] / peak["int8_ops"] == pytest.approx(41e-6,
                                                               rel=0.01)


def test_no_cost_for_a_store_without_one_byte_codes(ctx):
    store = copy.deepcopy(ctx["store"])
    store["arrays"]["codes"]["dtype"] = "uint8"      # a PQ store's
    with pytest.raises(ValueError, match="no SQ scan cost"):
        kernel_costs_sq.scan_cost(store, 1, 10)


def test_the_roofline_share_of_the_recorded_run(ctx):
    share = run.read_layer_metric("sq_scan_roofline_pct", ctx)
    assert share == pytest.approx(ctx["reported"], rel=1e-9)
    assert 0.0 < share < 100.0


def test_a_share_over_100_fails_the_run(ctx):
    ctx["trace"]["programs"]["jit_sq_topk"] = [1e-6, 8]
    with pytest.raises(RuntimeError, match="over 100"):
        run.read_layer_metric("sq_scan_roofline_pct", ctx)


@pytest.mark.parametrize("case", ["no-trace", "the-parent", "pq-codes",
                                  "bq-words", "other-width", "filtered",
                                  "no-scan-program", "nothing-dispatched"])
def test_the_roofline_reader_reads_none_where_there_is_nothing(ctx, case):
    ctx = dict(ctx, store=copy.deepcopy(ctx["store"]))
    if case == "no-trace":
        ctx["trace"] = None
    elif case == "the-parent":       # drops sq: a float32 flat store; also
        # a class still under its trainingLimit
        ctx["store"]["arrays"] = {"vectors": {"shape": [262144, 960],
                                              "dtype": "float32"}}
    elif case == "pq-codes":
        ctx["store"]["arrays"]["codes"] = {"shape": [262144, 96],
                                           "dtype": "uint8"}
    elif case == "bq-words":
        ctx["store"]["arrays"]["codes"] = {"shape": [131072, 24],
                                           "dtype": "uint32"}
    elif case == "other-width":
        ctx["store"]["arrays"]["codes"]["shape"] = [262144, 96]
    elif case == "filtered":
        ctx["mix"] = {"filter": {"property": "bucket"}}
    elif case == "no-scan-program":
        ctx["trace"] = {"programs": {"jit_chunked_topk_distances": [1.0, 4]}}
    else:
        ctx["trace_marks"] = dict(ctx["trace_marks"],
                                  after=ctx["trace_marks"]["before"])
    assert run.read_layer_metric("sq_scan_roofline_pct", ctx) is None


def test_compress_seconds_are_the_sq_stages_of_the_page_at_the_start():
    with open(os.path.join(HERE, "recorded",
                           "scrape_sq_window_start.prom")) as f:
        page = wire.Prom(f.read())
    got = run.read_layer_metric("sq_compress_s",
                                {"before": page, "after": wire.Prom("")})
    assert got == pytest.approx(0.2596436839994567 + 0.004154965001362143
                                + 0.14658877900001244)


@pytest.mark.parametrize("recorded", ["scrape_filtered_before.prom",
                                      "scrape_pq_window_start.prom"])
def test_without_the_sq_series_compress_seconds_read_none(recorded):
    """The parent's page has no compress series at all; a pq class's has
    them under another label."""
    with open(os.path.join(HERE, "recorded", recorded)) as f:
        page = wire.Prom(f.read())
    assert run.read_layer_metric("sq_compress_s",
                                 {"before": page, "after": page}) is None
