"""What PR 41 added to the benchmark, held by membership and not by
position: the configuration ``msmarco-8shard-4chip-cosine``, its one
four-chip cell and three per-layer metrics with their readers, on pages
of ``/v1/metrics`` and a recorded trace. Where the program labels no
device, as the parent does not, ``chip_dispatch_balance_pct`` reads None
and raises nothing."""

import json
import os

import pytest

import run
import wire

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = "msmarco-8shard-4chip-cosine"
CELL = CONFIG + ".c32"
SIBLING = "msmarco-8shard-cosine"
BUCKETS = "weaviate_tpu_query_batcher_compile_bucket_total"
PHASES = "weaviate_tpu_request_phase_seconds"
ADDED = {"chip_dispatch_balance_pct": ("program_counter", "query batcher",
                                       "qps", ".py"),
         "chip_scan_roofline_pct": ("device_trace", "kernels", "qps", ".py"),
         "chip_fanout_device_ms": ("program_span", "device program",
                                   "p50_ms", ".json")}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(run.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def config_of(name: str) -> dict:
    with open(os.path.join(run.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def device_page(counts: dict) -> wire.Prom:
    """``counts``: (padded batch, device label or None) -> dispatches."""
    lines = []
    for (b, device), n in counts.items():
        labels = f'b="{b}",k="16"' + (
            "" if device is None else f',device="{device}"')
        lines.append(f"{BUCKETS}{{{labels}}} {n}")
    return wire.Prom("\n".join(lines))


def test_the_configuration_and_its_one_cell_are_declared(bench):
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == ["rows"]
    cells = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert [w["name"] for w in cells] == [CELL]
    assert cells[0]["traffic"] == "nearvector-c32" and cells[0]["chips"] == 4
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    for text in (entry["why"], entry["source"], cells[0]["why"]):
        assert 0 < len(text) <= 200 and "\n" not in text and "\t" not in text
    cell, config, mix = run.find_cell(bench, CELL)
    assert cell is cells[0] and config["rows"] == 491520
    assert mix["clients"] == 32 and mix["filter"] is None


def test_the_configuration_is_the_one_chip_one_with_more_rows_and_chips():
    mine, sibling = config_of(CONFIG), config_of(SIBLING)
    for key in ("collection", "class", "dim", "metric", "k", "shards",
                "import_batch", "generator", "generator_params",
                "readback_sample", "scan_programs"):
        assert mine[key] == sibling[key], key
    for key in ("distance_error_max", "distance_scale_floor",
                "recall_at_k_min"):
        assert mine["limits"][key] == sibling["limits"][key]
    assert mine["precision"]["control_class_override"] == \
        sibling["precision"]["control_class_override"]
    assert set(mine["guarantees"]) == set(sibling["guarantees"])
    assert (mine["nodes"], mine["chips"], mine["shards_per_chip"]) == (1, 4, 2)
    assert mine["chips"] * mine["shards_per_chip"] == mine["shards"]
    assert mine["rows"] == mine["reduced"]["rows"]["here"] == 491520
    assert mine["class"]["shardingConfig"] == {"desiredCount": 8}


@pytest.mark.parametrize("name", sorted(ADDED))
def test_the_metrics_are_declared_for_the_four_chip_cell_only(bench, name):
    source, layer, moves, reader = ADDED[name]
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert (entry["source"], entry["layer"], entry["moves"]) == (
        source, layer, moves)
    assert os.path.exists(os.path.join(run.HERE, "layer_metrics",
                                       name + reader))
    reported = {m["name"] for m in run.metrics_of(bench, "per_layer", CELL)}
    assert name in reported
    elsewhere = {m["name"] for w in bench["workloads"] if w["name"] != CELL
                 for m in run.metrics_of(bench, "per_layer", w["name"])}
    assert name not in elsewhere


def test_every_metric_without_a_list_reports_in_the_cell_too(bench):
    reported = {m["name"] for m in run.metrics_of(bench, "per_layer", CELL)}
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} <= reported
    assert {"device_idle_pct", "launch_ms", "d2h_wait_ms",
            "batch_occupancy", "cpu_ms_per_search"} <= reported


@pytest.mark.parametrize("after,count,want", [
    # two shards a chip, each batcher dispatching alike
    ({(8, "tpu:0"): 100, (8, "tpu:1"): 100, (8, "tpu:2"): 100,
      (8, "tpu:3"): 100}, 4, 100.0),
    # sizes are summed a device; the least over the most
    ({(8, "tpu:0"): 60, (16, "tpu:0"): 40, (8, "tpu:1"): 80,
      (8, "tpu:2"): 50, (8, "tpu:3"): 90}, 4, 50.0),
    # a chip that served nothing reads 0
    ({(8, "tpu:0"): 100, (8, "tpu:1"): 100, (8, "tpu:2"): 100}, 4, 0.0),
    # the parent: no device label at all
    ({(8, None): 400}, 4, None),
    # a mesh's batcher labels the empty device: not a chip
    ({(8, ""): 400}, 4, None),
    # nothing dispatched in the window
    ({}, 4, None)])
def test_dispatch_balance(after, count, want):
    ctx = {"before": device_page({key: 10 for key in after}),
           "after": device_page({key: n + 10 for key, n in after.items()}),
           "device": {"count": count}}
    got = run.read_layer_metric("chip_dispatch_balance_pct", ctx)
    assert got == want if want is None else got == pytest.approx(want)


def test_dispatch_balance_reads_the_window_not_the_totals():
    ctx = {"before": device_page({(8, "tpu:0"): 1000, (8, "tpu:1"): 10}),
           "after": device_page({(8, "tpu:0"): 1100, (8, "tpu:1"): 60}),
           "device": {"count": 2}}
    assert run.read_layer_metric("chip_dispatch_balance_pct", ctx) == \
        pytest.approx(50.0)


def test_a_device_label_leaves_the_accepted_readers_as_they_were():
    """``Prom.total`` and ``by_label(.., "b")`` sum over the labels they do
    not name: ``batch_occupancy`` and the warm-up's bucket set read the
    same with and without the new label."""
    plain = device_page({(8, None): 30, (16, None): 10})
    labelled = device_page({(8, "tpu:0"): 10, (8, "tpu:1"): 20,
                            (16, "tpu:3"): 10})
    assert plain.total(BUCKETS) == labelled.total(BUCKETS) == 40
    assert plain.by_label(BUCKETS, "b") == labelled.by_label(BUCKETS, "b")


@pytest.fixture
def traced():
    """The recorded traced run of the one-chip sibling, as a four-chip
    run of the same program would read: the same executions spread over
    four planes sum to the same programs."""
    with open(os.path.join(HERE, "recorded",
                           "v5e_msmarco-8shard-cosine_c32_traced.json")) as f:
        rec = json.load(f)

    def page(counts):
        return device_page({(b, f"tpu:{i % 4}"): n
                            for i, (b, n) in enumerate(counts.items())})

    return {"trace": {"programs": rec["programs"]},
            "trace_marks": {"before": page(rec["buckets_before"]),
                            "after": page(rec["buckets_after"])},
            "store": rec["store"], "device": rec["device"],
            "config": config_of(CONFIG), "mix": {"filter": None}, "k": 10,
            "reported": rec["reported_pct"]}


def test_scan_roofline_is_the_one_chip_reading_over_all_planes(traced):
    got = run.read_layer_metric("chip_scan_roofline_pct", traced)
    assert got == pytest.approx(traced["reported"], rel=1e-6)
    assert 0 < got <= 100


def test_scan_roofline_reads_nothing_on_one_chip_or_without_rows(traced):
    one_chip = dict(traced, config=config_of(SIBLING))
    assert run.read_layer_metric("chip_scan_roofline_pct", one_chip) is None
    no_rows = dict(traced, store={"arrays": {}})
    assert run.read_layer_metric("chip_scan_roofline_pct", no_rows) is None
    assert run.read_layer_metric(
        "chip_scan_roofline_pct", dict(traced, trace=None)) is None


def test_fanout_device_is_the_critical_paths_phase():
    def page(total, count, phase="device"):
        labels = f'operation="grpc.search",phase="{phase}"'
        return wire.Prom(f"{PHASES}_sum{{{labels}}} {total}\n"
                         f"{PHASES}_count{{{labels}}} {count}")

    ctx = {"before": page(1.0, 100), "after": page(3.5, 200)}
    assert run.read_layer_metric("chip_fanout_device_ms", ctx) == \
        pytest.approx(25.0)
    quiet = {"before": page(1.0, 100), "after": page(1.0, 100)}
    assert run.read_layer_metric("chip_fanout_device_ms", quiet) is None
    other = {"before": page(1.0, 100, "host"), "after": page(3.5, 200, "host")}
    assert run.read_layer_metric("chip_fanout_device_ms", other) is None
