"""The six per-layer readers PR 34 added, on recorded input: the traced
chip run of ``msmarco-8shard-cosine.c32`` (programs, the first shard's
store, bucket counts summed over the eight batchers) for
``shard_scan_roofline_pct``, and pages of ``/v1/metrics`` for
``fanout_wait_ms``, ``merge_ms``, ``shard_batch_occupancy`` and the
critical path's two phases (``fanout_queue_wait_ms``,
``fanout_device_ms``). Where the program has no such stage, counter or
phase, as the parent has not, or the configuration has one shard, each
reads None and raises nothing."""

import copy
import json
import os

import pytest

import kernel_costs
import run
import wire

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "msmarco-8shard-cosine.c32"
BUCKETS = "weaviate_tpu_query_batcher_compile_bucket_total"
STAGES = "weaviate_tpu_request_stage_seconds"
FANNED = "weaviate_tpu_fanout_shards_total"
PHASES = "weaviate_tpu_request_phase_seconds"
ADDED = ["fanout_wait_ms", "merge_ms", "shard_scan_roofline_pct",
         "shard_batch_occupancy", "fanout_queue_wait_ms", "fanout_device_ms"]


def buckets_page(counts: dict) -> wire.Prom:
    return wire.Prom("\n".join(f'{BUCKETS}{{b="{b}",k="16"}} {n}'
                               for b, n in counts.items()))


def stage_page(stage: str, total: float, count: int) -> wire.Prom:
    labels = f'operation="grpc.search",stage="{stage}"'
    return wire.Prom(f"{STAGES}_sum{{{labels}}} {total}\n"
                     f"{STAGES}_count{{{labels}}} {count}")


def phase_page(phases: dict, operation: str = "grpc.search") -> wire.Prom:
    """``phases``: phase -> (sum, count) of one operation's histogram."""
    lines = []
    for phase, (total, count) in phases.items():
        labels = f'operation="{operation}",phase="{phase}"'
        lines += [f"{PHASES}_sum{{{labels}}} {total}",
                  f"{PHASES}_count{{{labels}}} {count}"]
    return wire.Prom("\n".join(lines))


@pytest.fixture
def ctx():
    with open(os.path.join(HERE, "recorded",
                           "v5e_msmarco-8shard-cosine_c32_traced.json")) as f:
        rec = json.load(f)
    with open(os.path.join(run.HERE, "configs",
                           "msmarco-8shard-cosine.json")) as f:
        config = json.load(f)
    return {"trace": {"programs": rec["programs"]},
            "trace_marks": {"before": buckets_page(rec["buckets_before"]),
                            "after": buckets_page(rec["buckets_after"])},
            "store": rec["store"], "device": rec["device"],
            "config": config, "mix": {"filter": None}, "k": config["k"],
            "reported": rec["reported_pct"]}


@pytest.mark.parametrize("name,source,layer,moves,reader", [
    ("fanout_wait_ms", "program_span", "wire and collection", "p50_ms",
     ".json"),
    ("merge_ms", "program_span", "wire and collection", "p50_ms", ".json"),
    ("shard_scan_roofline_pct", "device_trace", "kernels", "qps", ".py"),
    ("shard_batch_occupancy", "program_counter", "query batcher", "qps",
     ".py"),
    ("fanout_queue_wait_ms", "program_span", "query batcher", "p95_ms",
     ".json"),
    ("fanout_device_ms", "program_span", "device program", "p50_ms",
     ".json")])
def test_the_metrics_are_declared_for_the_eight_shard_cell_only(
        name, source, layer, moves, reader):
    with open(os.path.join(run.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = {m["name"]: m for m in bench["per_layer"]}[name]
    assert (m["source"], m["layer"], m["moves"]) == (source, layer, moves)
    assert m["workloads"] == [CELL]
    assert os.path.exists(os.path.join(run.HERE, "layer_metrics",
                                       name + reader))
    # the accepted rooflines and compress metrics keep their lists
    for kept in ("scan_roofline_pct", "pq_scan_roofline_pct",
                 "sq_scan_roofline_pct", "rescore_ms", "filter_ms"):
        assert CELL not in {m["name"]: m
                            for m in bench["per_layer"]}[kept]["workloads"]


def test_the_cell_and_its_configuration_are_declared():
    with open(os.path.join(run.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, config, mix = run.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "msmarco-8shard-cosine", "nearvector-c32", 1)
    assert len(cell["why"]) <= 200 and mix["filter"] is None
    entry = {c["name"]: c for c in bench["configs"]}["msmarco-8shard-cosine"]
    assert entry["reduced"] == ["rows"] == sorted(config["reduced"])
    assert (config["dim"], config["metric"], config["k"], config["shards"],
            config["nodes"]) == (768, "cosine", 10, 8, 1)
    assert config["class"]["shardingConfig"] == {"desiredCount": 8}
    assert "pq" not in config["class"]["vectorIndexConfig"]
    assert config["rows"] == 8 * 30720       # inside 32,768 slots a shard
    assert config["scan_programs"] == ["^jit_chunked_topk_distances$"]
    names = [m["name"] for m in run.metrics_of(bench, "per_layer", CELL)]
    assert names[-len(ADDED):] == ADDED
    # every unlisted metric the other cells report is this cell's too
    assert set(names[:-len(ADDED)]) == {m["name"] for m in bench["per_layer"]
                                        if "workloads" not in m}


@pytest.mark.parametrize("accepted,new", [
    ("queue_wait_ms", "fanout_queue_wait_ms"),
    ("device_phase_ms", "fanout_device_ms")])
def test_a_phase_the_parent_does_not_charge_is_listed_for_the_old_cells(
        accepted, new):
    """A program that fans out through pool threads charges a request no
    phase, so the accepted reader finds nothing on the parent in this
    cell: the accepted metric lists the cells the benchmark had, and the
    same series is read here under a name this PR adds."""
    with open(os.path.join(run.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name[accepted]["workloads"] == [
        w["name"] for w in bench["workloads"] if w["name"] != CELL]
    assert by_name[new]["moves"] == by_name[accepted]["moves"]
    specs = []
    for name in (accepted, new):
        with open(os.path.join(run.HERE, "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        specs.append({key: spec[key]
                      for key in ("kind", "series", "labels", "scale")})
    assert specs[0] == specs[1]


@pytest.mark.parametrize("name,phase", [("fanout_queue_wait_ms", "queue_wait"),
                                        ("fanout_device_ms", "device")])
def test_a_phase_reader_reads_the_mean_of_the_critical_paths_phase(
        name, phase):
    other = "device" if phase == "queue_wait" else "queue_wait"
    got = run.read_layer_metric(name, {
        "before": phase_page({phase: (2.0, 100), other: (9.0, 100)}),
        "after": phase_page({phase: (2.0 + 0.360, 100 + 10),
                             other: (99.0, 110)})})
    assert got == pytest.approx(36.0)


@pytest.mark.parametrize("name", ["fanout_queue_wait_ms", "fanout_device_ms"])
@pytest.mark.parametrize("page", ["the-parent", "nothing-in-the-window"])
def test_where_no_phase_is_charged_the_phase_readers_read_none(name, page):
    """The parent's Searches over eight shards wait in pool threads that
    carry no timeline: its page has the phases of other operations only."""
    if page == "the-parent":
        before = after = phase_page({"queue_wait": (1.0, 40),
                                     "device": (1.0, 40)},
                                    operation="rest.graphql")
    else:
        before = after = phase_page({"queue_wait": (1.0, 40),
                                     "device": (1.0, 40)})
    assert run.read_layer_metric(name, {"before": before,
                                        "after": after}) is None


@pytest.mark.parametrize("name,stage", [("fanout_wait_ms", "fanout_wait"),
                                        ("merge_ms", "merge")])
def test_a_stage_reader_reads_the_mean_of_its_stage_in_ms(name, stage):
    got = run.read_layer_metric(name, {
        "before": stage_page(stage, 1.5, 100),
        "after": stage_page(stage, 1.5 + 0.240, 100 + 60)})
    assert got == pytest.approx(4.0)


@pytest.mark.parametrize("name", ["fanout_wait_ms", "merge_ms"])
@pytest.mark.parametrize("page", ["the-parent", "one-shard-requests"])
def test_without_a_fan_out_the_stage_readers_read_none(name, page):
    """The parent's page has no such stage; on the change a window of
    one-shard requests observes neither."""
    if page == "the-parent":
        with open(os.path.join(HERE, "recorded",
                               "scrape_filtered_after.prom")) as f:
            before = after = wire.Prom(f.read())
    else:
        before = after = stage_page(name[:-3], 0.75, 50)
    assert run.read_layer_metric(name, {"before": before,
                                        "after": after}) is None


def occupancy_page(counts: dict, fanned: int | None) -> wire.Prom:
    page = "\n".join(f'{BUCKETS}{{b="{b}",k="16"}} {n}'
                     for b, n in counts.items())
    if fanned is not None:
        page += f'\n{FANNED}{{collection="Passages"}} {fanned}'
    return wire.Prom(page)


def test_shard_searches_a_dispatch_over_all_the_batchers():
    """320 requests over eight shards in 580 dispatches of the eight
    batchers: 4.4 shard searches a dispatch, where ``batch_occupancy``
    reads the 320 requests over the same 580."""
    got = run.read_layer_metric("shard_batch_occupancy", {
        "before": occupancy_page({"4": 100, "8": 20}, 800),
        "after": occupancy_page({"4": 500, "8": 200}, 800 + 320 * 8)})
    assert got == pytest.approx(320 * 8 / 580)


@pytest.mark.parametrize("case", ["the-parent", "one-shard-requests",
                                  "nothing-dispatched"])
def test_without_a_fan_out_the_occupancy_reader_reads_none(case):
    fanned = {"the-parent": (None, None), "one-shard-requests": (0, 0),
              "nothing-dispatched": (800, 1600)}[case]
    after = {"4": 100} if case == "nothing-dispatched" else {"4": 500}
    assert run.read_layer_metric("shard_batch_occupancy", {
        "before": occupancy_page({"4": 100}, fanned[0]),
        "after": occupancy_page(after, fanned[1])}) is None


def test_the_scan_is_costed_at_one_shards_shapes(ctx):
    assert ctx["store"]["arrays"]["vectors"] == {"shape": [32768, 768],
                                                 "dtype": "float32"}
    cost = kernel_costs.scan_cost(ctx["store"], 16, 10)
    assert cost["bytes"] == 32768 * 768 * 4 + 16 * 768 * 4 + 16 * 10 * 8
    assert cost["flops"] == 2.0 * 16 * 32768 * 768
    seconds, by = kernel_costs.least_seconds(
        cost, kernel_costs.peaks("TPU v5 lite"))
    assert by == "bytes" and 0.12e-3 < seconds < 0.13e-3


def test_the_roofline_share_of_the_recorded_run(ctx):
    share = run.read_layer_metric("shard_scan_roofline_pct", ctx)
    assert share == pytest.approx(ctx["reported"], rel=1e-9)
    assert 0.0 < share < 100.0
    # the same arithmetic as the one-shard reader's, on the same operands
    assert share == run.read_layer_metric("scan_roofline_pct", ctx)


def test_a_share_over_100_fails_the_run(ctx):
    ctx["trace"]["programs"]["jit_chunked_topk_distances"] = [1e-6, 64]
    with pytest.raises(RuntimeError, match="over 100"):
        run.read_layer_metric("shard_scan_roofline_pct", ctx)


@pytest.mark.parametrize("case", ["no-trace", "one-shard", "no-float-rows",
                                  "filtered", "no-scan-program",
                                  "nothing-dispatched"])
def test_the_roofline_reader_reads_none_where_there_is_nothing(ctx, case):
    ctx = dict(ctx, store=copy.deepcopy(ctx["store"]),
               config=copy.deepcopy(ctx["config"]))
    if case == "no-trace":
        ctx["trace"] = None
    elif case == "one-shard":        # scan_roofline_pct's cell, not this
        ctx["config"]["shards"] = 1
    elif case == "no-float-rows":
        ctx["store"]["arrays"] = {"codes": {"shape": [32768, 24],
                                            "dtype": "uint32"}}
    elif case == "filtered":
        ctx["mix"] = {"filter": {"property": "bucket"}}
    elif case == "no-scan-program":
        ctx["trace"] = {"programs": {"jit_sq_topk": [1.0, 4]}}
    else:
        ctx["trace_marks"] = dict(ctx["trace_marks"],
                                  after=ctx["trace_marks"]["before"])
    assert run.read_layer_metric("shard_scan_roofline_pct", ctx) is None
