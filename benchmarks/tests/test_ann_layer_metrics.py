"""What PR 43 added to the benchmark, held by membership and not by
position (a later PR appends after these entries): the configuration
``glove-dynamic-cosine`` and its one cell, the five per-layer readers on a
recorded chip run (``recorded/v5e_glove-dynamic-cosine_c32_traced.json``:
the trace's programs, ``describe``'s store, the ``weaviate_tpu_ivf_*``
series of the three pages a traced run reads) and the probe's costs. Where
the program has no such series or store, as the parent has not, each
reader reads None and raises nothing."""

import copy
import json
import os

import pytest

import kernel_costs
import kernel_costs_ivf
import run
import wire

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "glove-dynamic-cosine.c32"
BUCKETS = "weaviate_tpu_query_batcher_compile_bucket_total"
PHASE = "weaviate_tpu_request_phase_seconds"
ADDED = {
    "ivf_probe_roofline_pct": ("%", "higher", "device_trace", "kernels",
                               "qps", ".py"),
    "ivf_scanned_share_pct": ("%", "lower", "program_counter",
                              "device program", "qps", ".py"),
    "ann_device_ms": ("ms", "lower", "program_span", "device program",
                      "p50_ms", ".json"),
    "ann_queue_wait_ms": ("ms", "lower", "program_span", "query batcher",
                          "p95_ms", ".json"),
    "ivf_build_s": ("s", "lower", "program_span",
                    "set-up, off the request path", "setup_s", ".py"),
}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(run.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded",
                           "v5e_glove-dynamic-cosine_c32_traced.json")) as f:
        return json.load(f)


def page(series: dict, extra: str = "") -> wire.Prom:
    """A scraped page from the recorded series (key = name less the
    prefix, then the label values: a stage, or collection and shard)."""
    out = [extra]
    for key, value in series.items():
        name, *labels = key.split(".")
        names = ("stage",) if "maintain" in name else ("collection", "shard")
        text = ",".join(f'{n}="{v}"' for n, v in zip(names, labels))
        out.append(f"weaviate_tpu_{name}{{{text}}} {value}" if text
                   else f"weaviate_tpu_{name} {value}")
    return wire.Prom("\n".join(out))


def buckets(counts: dict) -> str:
    return "\n".join(f'{BUCKETS}{{b="{b}",k="16",device=""}} {n}'
                     for b, n in counts.items())


@pytest.fixture
def ctx(recorded):
    with open(os.path.join(run.HERE, "configs",
                           "glove-dynamic-cosine.json")) as f:
        config = json.load(f)
    pages = recorded["pages"]
    moved = recorded["buckets_moved"]
    return {"before": page(pages["window_start"]),
            "after": page(pages["trace_before"]),
            "trace": {"programs": copy.deepcopy(recorded["programs"])},
            "trace_marks": {
                "before": page(pages["trace_before"],
                               buckets({b: 0 for b in moved})),
                "after": page(pages["trace_after"], buckets(moved))},
            "store": copy.deepcopy(recorded["store"]),
            "device": recorded["device"], "config": config,
            "mix": {"filter": None}, "k": config["k"]}


# -- what BENCHMARK.json gained ------------------------------------------------


def test_the_configuration_and_its_one_cell_are_declared(bench):
    cell, config, mix = run.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glove-dynamic-cosine", "nearvector-c32", 1)
    assert len(cell["why"]) <= 200 and mix["filter"] is None
    entry = {c["name"]: c for c in bench["configs"]}["glove-dynamic-cosine"]
    assert entry["file"] == "benchmarks/configs/glove-dynamic-cosine.json"
    assert entry["reduced"] == ["rows"] == sorted(config["reduced"])
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    for word in ("glove-100-angular", "BASELINE.json config 2", "dynamic",
                 "threshold 10000"):
        assert word in entry["source"], word
    assert [c["name"] for c in bench["workloads"]
            if c["config"] == "glove-dynamic-cosine"] == [CELL]
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1


def test_the_configuration_file_states_the_deployment():
    with open(os.path.join(run.HERE, "configs",
                           "glove-dynamic-cosine.json")) as f:
        config = json.load(f)
    assert (config["dim"], config["metric"], config["k"], config["shards"],
            config["nodes"], config["import_batch"], config["rows"]) == (
        100, "cosine", 10, 1, 1, 1024, 262144)
    assert config["class"] == {
        "class": "Glove", "vectorIndexType": "dynamic",
        "vectorIndexConfig": {"distance": "cosine", "threshold": 10000},
        "properties": [{"name": "bucket", "dataType": ["int"]}]}
    assert config["generator"] == "clustered"
    assert config["generator_params"] == {
        "members": 32, "spread": 0.35, "queries": 4096,
        "int_props": {"bucket": [0, 100]}}
    assert config["limits"]["distance_error_max"] == 1e-4
    assert config["limits"]["distance_scale_floor"] == 0.01
    assert config["limits"]["recall_at_k_min"] == 0.95
    assert config["reduced"]["rows"]["source"] == 1183514
    assert config["precision"]["next_lower"] == "bfloat16"
    assert config["precision"]["control_class_override"] == {
        "storage_dtype": "bfloat16"}
    assert set(config["guarantees"]) >= {"read_your_writes", "durability",
                                         "answers"}
    assert set(config["assumed"]) >= {"data", "ann_index", "nlist_nprobe",
                                      "async_indexing", "bucket"}
    assert config["scan_programs"] == ["^jit__ivf_probe_topk$"]
    assert os.path.exists(os.path.join(run.HERE, "datagen",
                                       config["generator"] + ".py"))


@pytest.mark.parametrize("name", sorted(ADDED))
def test_each_metric_is_declared_for_the_cell_only_with_a_reader(bench, name):
    unit, better, source, layer, moves, ext = ADDED[name]
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert m == {"name": name, "unit": unit, "better": better,
                 "source": source, "layer": layer, "moves": moves,
                 "workloads": [CELL]}
    assert layer in {x["layer"] for x in bench["per_layer"]
                     if x["name"] not in ADDED}     # a layer PERF.md has
    assert os.path.exists(os.path.join(run.HERE, "layer_metrics",
                                       name + ext))
    assert m in run.metrics_of(bench, "per_layer", CELL)
    assert all(m not in run.metrics_of(bench, "per_layer", c["name"])
               for c in bench["workloads"] if c["name"] != CELL)


def test_no_accepted_list_names_the_cell(bench):
    """``device_phase_ms``, ``queue_wait_ms`` and the scan rooflines keep
    their lists: the cell reads those series under names of its own."""
    for m in bench["per_layer"]:
        if m["name"] not in ADDED:
            assert CELL not in m.get("workloads", []), m["name"]


# -- the probe's costs -----------------------------------------------------------


def test_the_probe_cost_is_the_algorithms(recorded):
    store = recorded["store"]
    assert store["arrays"]["list_vecs"] == {"shape": [1024, 512, 100],
                                            "dtype": "float32"}
    nlist, cap, dim, nprobe, k = 1024, 512, 100, 128, 10
    row = dim * 4 + 4 + 4 + 1
    for b in (1, 16, 32):
        cost = kernel_costs_ivf.probe_cost(store, b, k, nprobe)
        assert cost["int_ops"] == 0.0
        assert cost["flops"] == (2.0 * b * nlist * dim
                                 + 2.0 * b * nprobe * cap * dim)
        # a list two queries of one dispatch both probe is read once:
        # from b = 8 on a dispatch can touch every list, and no more
        assert cost["bytes"] == (nlist * dim * 4
                                 + min(b * nprobe, nlist) * cap * row
                                 + b * dim * 4 + b * k * 8)
    peak = kernel_costs.peaks("TPU v5 lite")
    seconds, by = kernel_costs.least_seconds(
        kernel_costs_ivf.probe_cost(store, 16, k, nprobe), peak)
    # 214.9 MB (the whole store once) against 0.21 GFLOP: bytes-bound
    assert by == "bytes" and 0.26e-3 < seconds < 0.27e-3
    one, _ = kernel_costs.least_seconds(
        kernel_costs_ivf.probe_cost(store, 1, k, nprobe), peak)
    assert one == pytest.approx(seconds / 8, rel=0.02)


def test_no_cost_for_a_store_without_posting_lists(recorded):
    store = copy.deepcopy(recorded["store"])
    del store["arrays"]["list_vecs"]       # residual-PQ lists hold codes
    with pytest.raises(ValueError, match="no IVF probe cost"):
        kernel_costs_ivf.probe_cost(store, 1, 10, 8)


# -- the readers on the recorded run -----------------------------------------------


def test_the_roofline_share_of_the_recorded_run(ctx, recorded):
    share = run.read_layer_metric("ivf_probe_roofline_pct", ctx)
    assert share == pytest.approx(
        recorded["reported"]["ivf_probe_roofline_pct"], rel=1e-9)
    assert 0.0 < share <= 100.0
    note = recorded["roofline_note"]
    # 72 programs were 69.2 dispatches: the four of padded batch 32 ran
    # as two programs each
    assert note["executions"] == 72
    assert note["dispatches"] == pytest.approx(72 * 98 / 102)
    assert note["nprobe"] == 128.0


def test_a_share_over_100_fails_the_run(ctx):
    ctx["trace"]["programs"]["jit__ivf_probe_topk"] = [1e-6, 72]
    with pytest.raises(RuntimeError, match="over 100"):
        run.read_layer_metric("ivf_probe_roofline_pct", ctx)


@pytest.mark.parametrize("case", ["no-trace", "the-parents-counters",
                                  "a-flat-store", "no-probe-program",
                                  "nothing-dispatched"])
def test_the_roofline_reader_reads_none_where_there_is_nothing(ctx, case,
                                                               recorded):
    if case == "no-trace":
        ctx["trace"] = None
    elif case == "the-parents-counters":   # it runs the cell, counts nothing
        moved = recorded["buckets_moved"]
        ctx["trace_marks"] = {
            "before": wire.Prom(buckets({b: 0 for b in moved})),
            "after": wire.Prom(buckets(moved))}
    elif case == "a-flat-store":           # a class under its threshold
        ctx["store"]["arrays"] = {"vectors": {"shape": [8192, 100],
                                              "dtype": "float32"}}
    elif case == "no-probe-program":
        ctx["trace"] = {"programs": {"jit_chunked_topk_distances": [1.0, 4]}}
    else:
        ctx["trace_marks"]["after"] = ctx["trace_marks"]["before"]
    assert run.read_layer_metric("ivf_probe_roofline_pct", ctx) is None


def test_the_scanned_share_and_the_build_seconds(ctx, recorded):
    # 128 lists of 512 positions a query over 262,144 live rows
    assert run.read_layer_metric("ivf_scanned_share_pct", ctx) == \
        pytest.approx(100.0 * 128 * 512 / 262144) == \
        recorded["reported"]["ivf_scanned_share_pct"]
    build = run.read_layer_metric("ivf_build_s", ctx)
    assert build == pytest.approx(recorded["reported"]["ivf_build_s"])
    start = recorded["pages"]["window_start"]
    assert build == pytest.approx(sum(
        start[f"ivf_maintain_seconds_sum.{stage}"]
        for stage in ("upgrade", "train", "flush")))


def test_the_series_readers_read_none_on_the_parent(ctx):
    nothing = wire.Prom("weaviate_tpu_objects_total 262144")
    ctx = dict(ctx, before=nothing, after=nothing)
    assert run.read_layer_metric("ivf_scanned_share_pct", ctx) is None
    assert run.read_layer_metric("ivf_build_s", ctx) is None
    assert run.read_layer_metric("ann_device_ms", ctx) is None
    assert run.read_layer_metric("ann_queue_wait_ms", ctx) is None


@pytest.mark.parametrize("name,phase", [("ann_device_ms", "device"),
                                        ("ann_queue_wait_ms", "queue_wait")])
def test_the_phase_readers_read_the_search_phases_of_the_window(ctx, name,
                                                                phase):
    def phases(total_s, n):
        return wire.Prom("\n".join(
            f'{PHASE}_{part}{{operation="grpc.search",phase="{ph}"}} {v}'
            for ph, scale in (("device", 1.0), ("queue_wait", 0.5),
                              ("host", 9.0))
            for part, v in (("sum", total_s * scale), ("count", n))))

    ctx = dict(ctx, before=phases(1.0, 100), after=phases(1.0 + 0.47, 110))
    want = {"device": 47.0, "queue_wait": 23.5}[phase]
    assert run.read_layer_metric(name, ctx) == pytest.approx(want)
    with open(os.path.join(run.HERE, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["labels"] == {"operation": "grpc.search", "phase": phase}
