"""What PR 51 added to the benchmark, held by membership and not by
position (a later PR appends after these entries): the configuration
``cohere-dynamic-cosine`` and its one cell, the eight ``annf_*`` per-layer
readers over pages and a trace written out here by hand (the series a
traced run scrapes, the two programs' module time), and the two routes'
costs. Where the program has no such series, as the parent has not, each
reader that reads a new series reads None and raises nothing."""

import json
import os

import pytest

import kernel_costs
import kernel_costs_ivf
import kernel_costs_ivf_filtered
import run
import wire

CELL = "cohere-dynamic-cosine.filtered-c32"
BUCKETS = "weaviate_tpu_query_batcher_compile_bucket_total"
ADDED = {
    "annf_cutoff_share_pct": ("%", "higher", "program_counter",
                              "device program", "qps", ".py"),
    "annf_cutoff_roofline_pct": ("%", "higher", "device_trace", "kernels",
                                 "qps", ".py"),
    "annf_probe_roofline_pct": ("%", "higher", "device_trace", "kernels",
                                "qps", ".py"),
    "annf_operand_hit_pct": ("%", "higher", "program_counter",
                             "query batcher", "qps", ".py"),
    "annf_device_ms": ("ms", "lower", "program_span", "device program",
                       "p50_ms", ".json"),
    "annf_queue_wait_ms": ("ms", "lower", "program_span", "query batcher",
                           "p95_ms", ".json"),
    "annf_filter_ms": ("ms", "lower", "program_span", "wire and collection",
                       "p50_ms", ".json"),
    "annf_build_s": ("s", "lower", "program_span",
                     "set-up, off the request path", "setup_s", ".py"),
}
STORE = {"index": "DynamicIndex", "store": "IVFStore", "capacity": 262144,
         "arrays": {"list_vecs": {"shape": [1024, 512, 768],
                                  "dtype": "float32"}}}
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(run.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(run.HERE, "configs",
                           "cohere-dynamic-cosine.json")) as f:
        return json.load(f)


def page(**series) -> wire.Prom:
    """A scraped page: ``name__label_value=number`` for a labelled series
    (route, result or b), ``name=number`` for a plain one."""
    lines = []
    for key, value in series.items():
        name, _, label = key.partition("__")
        if not label:
            lines.append(f"weaviate_tpu_{name} {value}")
            continue
        what = {"ivf_filtered_requests_total": "route",
                "filter_operand_total": "result",
                "query_batcher_compile_bucket_total": "b"}[name]
        lines.append(f'weaviate_tpu_{name}{{{what}="{label}"}} {value}')
    return wire.Prom("\n".join(lines))


def traced(config, before: wire.Prom, after: wire.Prom, programs: dict):
    return {"before": before, "after": after,
            "trace": {"programs": programs},
            "trace_marks": {"before": before, "after": after},
            "store": STORE, "device": DEVICE, "config": config,
            "mix": {"filter": {"property": "bucket"}}, "k": config["k"]}


# -- what BENCHMARK.json gained ------------------------------------------------


def test_the_configuration_and_its_one_cell_are_declared(bench, config):
    cell, found, mix = run.find_cell(bench, CELL)
    assert found == config
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "cohere-dynamic-cosine", "filtered-c32", 1)
    assert len(cell["why"]) <= 200
    assert mix["filter"]["values"] == [1, 10, 50, 99]
    entry = {c["name"]: c for c in bench["configs"]}["cohere-dynamic-cosine"]
    assert entry["file"] == "benchmarks/configs/cohere-dynamic-cosine.json"
    assert entry["reduced"] == ["rows"] == sorted(config["reduced"])
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for word in ("Performance768D1M1P", "Performance768D1M99P", "dynamic",
                 "threshold 10000", "flatSearchCutoff 40000"):
        assert word in entry["source"], word
    assert [c["name"] for c in bench["workloads"]
            if c["config"] == "cohere-dynamic-cosine"] == [CELL]
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1


def test_the_configuration_file_states_the_deployment(config):
    assert (config["dim"], config["metric"], config["k"], config["shards"],
            config["nodes"], config["import_batch"]) == (
        768, "cosine", 100, 1, 1, 1024)
    assert config["rows"] in (262144, 131072)      # ISSUE 51's two sizes
    assert config["rows"] == config["reduced"]["rows"]["here"]
    assert config["reduced"]["rows"]["source"] == 1000000
    assert config["class"] == {
        "class": "CohereDynamic", "vectorIndexType": "dynamic",
        "vectorIndexConfig": {"distance": "cosine", "threshold": 10000,
                              "hnsw": {"flatSearchCutoff": 40000}},
        "properties": [{"name": "bucket", "dataType": ["int"]}]}
    assert config["generator_params"] == {
        "members": 128, "spread": 0.35, "queries": 4096,
        "int_props": {"bucket": [0, 100]}}
    assert config["limits"]["distance_error_max"] == 1e-4
    assert config["limits"]["distance_scale_floor"] == 0.01
    assert config["limits"]["recall_at_k_min"] == 0.95
    assert config["precision"]["control_class_override"] == {
        "storage_dtype": "bfloat16"}
    assert set(config["guarantees"]) >= {
        "read_your_writes", "durability", "answers", "filtered_answers"}
    assert set(config["assumed"]) >= {"data", "ann_index", "nlist_nprobe",
                                      "async_indexing", "flat_search_cutoff"}
    assert config["scan_programs"] == config["probe_programs"] \
        + config["cutoff_programs"]
    # two of the mix's bounds on each side of the cutoff at this size
    allowed = [config["rows"] * b // 100 for b in (1, 10, 50, 99)]
    assert [a < 40000 for a in allowed] == [True, True, False, False]


@pytest.mark.parametrize("name", sorted(ADDED))
def test_a_metric_is_declared_for_this_cell_alone(bench, name):
    unit, better, source, layer, moves, ext = ADDED[name]
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": [CELL]}
    assert os.path.exists(os.path.join(run.HERE, "layer_metrics",
                                       name + ext))
    assert layer in {m["layer"] for m in bench["per_layer"]
                     if not m["name"].startswith("annf_")}


# -- the readers ------------------------------------------------------------------


def test_cutoff_share_is_the_exact_routes_share_of_filtered_requests(config):
    ctx = traced(config,
                 page(ivf_filtered_requests_total__flat_cutoff=100,
                      ivf_filtered_requests_total__probe=40),
                 page(ivf_filtered_requests_total__flat_cutoff=1300,
                      ivf_filtered_requests_total__probe=1240), {})
    assert run.read_layer_metric("annf_cutoff_share_pct", ctx) == 50.0
    ctx = traced(config, page(), page(), {})          # the parent
    assert run.read_layer_metric("annf_cutoff_share_pct", ctx) is None


def test_operand_hit_counts_hits_and_shared_rows_over_all_lookups(config):
    ctx = traced(config,
                 page(filter_operand_total__miss=4),
                 page(filter_operand_total__miss=4,
                      filter_operand_total__hit=300,
                      filter_operand_total__shared=700), {})
    assert run.read_layer_metric("annf_operand_hit_pct", ctx) == 100.0
    ctx = traced(config, page(),
                 page(filter_operand_total__uncached=500), {})  # the parent
    assert run.read_layer_metric("annf_operand_hit_pct", ctx) == 0.0
    ctx = traced(config, page(), page(), {})
    assert run.read_layer_metric("annf_operand_hit_pct", ctx) is None


def test_build_s_reads_the_window_starts_page(config):
    before = wire.Prom(
        'weaviate_tpu_ivf_maintain_seconds_sum{stage="train"} 20.5\n'
        'weaviate_tpu_ivf_maintain_seconds_sum{stage="flush"} 10.25\n')
    ctx = traced(config, before, page(), {})
    assert run.read_layer_metric("annf_build_s", ctx) == 30.75
    ctx = traced(config, page(), page(), {})
    assert run.read_layer_metric("annf_build_s", ctx) is None


@pytest.mark.parametrize("name,phase", [("annf_device_ms", "phase"),
                                        ("annf_queue_wait_ms", "phase"),
                                        ("annf_filter_ms", "stage")])
def test_the_three_means_read_the_request_series(config, name, phase):
    with open(os.path.join(run.HERE, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["kind"] == "prom_mean" and phase in spec["labels"]
    labels = ",".join(f'{k}="{v}"' for k, v in spec["labels"].items())

    def scraped(total, count):
        return wire.Prom(f"{spec['series']}_sum{{{labels}}} {total}\n"
                         f"{spec['series']}_count{{{labels}}} {count}\n")

    ctx = traced(config, scraped(1.0, 100), scraped(3.0, 200), {})
    assert run.read_layer_metric(name, ctx) == pytest.approx(20.0)
    ctx = traced(config, scraped(1.0, 100), scraped(1.0, 100), {})
    assert run.read_layer_metric(name, ctx) is None


def test_cutoff_roofline_costs_the_allowed_rows_once(config, capsys):
    """40 traced programs of 14,400 allowed rows each for blocks of 32:
    44.3 MB a program, bytes-bound."""
    before = page(ivf_cutoff_rows_total=0, ivf_cutoff_programs_total=0,
                  query_batcher_compile_bucket_total__32=0)
    after = page(ivf_cutoff_rows_total=14400 * 50,
                 ivf_cutoff_programs_total=50,
                 query_batcher_compile_bucket_total__32=25)
    ctx = traced(config, before, after,
                 {"jit__ivf_flat_cutoff_topk": (0.040, 40),
                  "jit__ivf_probe_topk": (0.5, 30)})
    share = run.read_layer_metric("annf_cutoff_roofline_pct", ctx)
    cost = kernel_costs_ivf_filtered.cutoff_cost(STORE, 32, 100, 14400)
    assert cost["bytes"] == 14400 * (768 * 4 + 4) + 32 * 768 * 4 + 32 * 800
    assert cost["flops"] == 2.0 * 32 * 14400 * 768
    least, by = kernel_costs.least_seconds(
        cost, kernel_costs.peaks("TPU v5 lite"))
    assert by == "bytes"
    assert share == pytest.approx(100.0 * 40 * least / 0.040)
    assert 0 < share < 100
    assert '"annf_cutoff_roofline"' in capsys.readouterr().out
    # a count that is too high fails the run
    ctx["trace"]["programs"]["jit__ivf_flat_cutoff_topk"] = (0.001, 40)
    with pytest.raises(RuntimeError, match="over 100"):
        run.read_layer_metric("annf_cutoff_roofline_pct", ctx)
    # the parent: no such counters, no such program
    ctx = traced(config, page(), page(),
                 {"jit__ivf_probe_topk": (0.5, 30)})
    assert run.read_layer_metric("annf_cutoff_roofline_pct", ctx) is None


def test_probe_roofline_costs_the_rows_that_probed(config):
    """30 traced probe programs, 1.5 a dispatch, 24 rows a dispatch: a
    dispatch reads the whole store once (24 x 128 lists > 1,024)."""
    before = page(ivf_probe_programs_total=0, ivf_probe_dispatches_total=0,
                  ivf_queries_total=0, ivf_probed_lists_total=0)
    after = page(ivf_probe_programs_total=60, ivf_probe_dispatches_total=40,
                 ivf_queries_total=40 * 24,
                 ivf_probed_lists_total=40 * 24 * 128)
    ctx = traced(config, before, after,
                 {"jit__ivf_probe_topk": (0.5, 30),
                  "jit__ivf_flat_cutoff_topk": (0.040, 40)})
    share = run.read_layer_metric("annf_probe_roofline_pct", ctx)
    cost = kernel_costs_ivf_filtered.probe_cost(STORE, 24, 100, 128)
    assert cost == kernel_costs_ivf.probe_cost(STORE, 24, 100, 128)
    least, by = kernel_costs.least_seconds(
        cost, kernel_costs.peaks("TPU v5 lite"))
    assert by == "bytes"
    assert share == pytest.approx(100.0 * 20 * least / 0.5)
    assert 0 < share < 100
    ctx = traced(config, page(), page(),
                 {"jit__ivf_probe_topk": (0.5, 30)})   # the parent
    assert run.read_layer_metric("annf_probe_roofline_pct", ctx) is None
