"""The per-layer readers PR 25 added, on recorded scrapes: two pages of
``/v1/metrics`` (cut to the series the readers use) around 128 filtered
Searches from 8 threads against an 8,192-row ``sift-flat-l2`` class on
the CPU backend (counts and host-clock seconds: no device number is read
from them). On a page without the two stage families, as a parent before
PR 25 serves, every new reader returns None and raises nothing."""

import json
import os
import re

import pytest

import run
import wire

HERE = os.path.dirname(os.path.abspath(__file__))
STAGE_FAMILIES = re.compile(
    r"^weaviate_tpu_(request|dispatch)_stage_seconds", re.M)
REQUEST = ("server_residency_ms", "pool_wait_ms", "handler_cpu_ms",
           "filter_ms", "fetch_ms", "reply_ms")
DISPATCH = ("mask_pack_ms", "launch_ms", "d2h_wait_ms")
NEW = REQUEST + DISPATCH + ("rescore_ms", "dispatch_busy_pct")


def page(name: str, without_stages: bool = False) -> wire.Prom:
    with open(os.path.join(HERE, "recorded", name)) as f:
        text = f.read()
    if without_stages:
        text = "\n".join(ln for ln in text.splitlines()
                         if not STAGE_FAMILIES.match(ln))
    return wire.Prom(text)


@pytest.fixture(scope="module")
def ctx():
    return {"before": page("scrape_filtered_before.prom"),
            "after": page("scrape_filtered_after.prom"),
            "mix": {"filter": {"property": "bucket"}}}


def delta(ctx, series, labels):
    return (ctx["after"].total(series, labels)
            - ctx["before"].total(series, labels))


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_is_declared_with_a_reader(name):
    with open(os.path.join(run.REPO, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert name in declared
    base = os.path.join(run.HERE, "layer_metrics", name)
    assert os.path.exists(base + ".json") != os.path.exists(base + ".py")
    assert declared[name]["source"] == (
        "program_counter" if name == "dispatch_busy_pct" else "program_span")


@pytest.mark.parametrize("name", REQUEST)
def test_request_stage_readers_read_the_mean_of_their_stage(ctx, name):
    stage = name[:-3]
    series = "weaviate_tpu_request_stage_seconds"
    labels = {"operation": "grpc.search", "stage": stage}
    searches = delta(ctx, series + "_count", labels)
    assert searches == 128
    want = delta(ctx, series + "_sum", labels) / searches * 1000.0
    assert run.read_layer_metric(name, ctx) == pytest.approx(want)
    assert want > 0


def test_request_stages_sum_to_the_residency(ctx):
    series = "weaviate_tpu_request_stage_seconds_sum"
    additive = ("pool_wait", "parse", "filter", "queue_wait", "device",
                "transfer", "wake", "fetch", "search_other", "reply", "send")
    total = sum(delta(ctx, series, {"stage": s}) for s in additive)
    assert total == pytest.approx(
        delta(ctx, series, {"stage": "server_residency"}), rel=1e-9)


@pytest.mark.parametrize("name", DISPATCH)
def test_dispatch_stage_readers_read_ms_a_dispatch(ctx, name):
    series = "weaviate_tpu_dispatch_stage_seconds"
    labels = {"stage": name[:-3]}
    dispatches = delta(ctx, series + "_count", labels)
    assert dispatches > 0
    assert run.read_layer_metric(name, ctx) == pytest.approx(
        delta(ctx, series + "_sum", labels) / dispatches * 1000.0)


def test_solo_and_coalesced_dispatches_are_told_apart(ctx):
    """b = 1 of the mix goes solo: its launches are a kind of their own,
    and only coalesced dispatches pack a mask."""
    series = "weaviate_tpu_dispatch_stage_seconds_count"
    solo = delta(ctx, series, {"kind": "flat.solo", "stage": "launch"})
    assert solo == 32                      # a quarter of 128 requests
    assert delta(ctx, series, {"kind": "flat.solo",
                               "stage": "mask_pack"}) == 0
    assert delta(ctx, series, {"kind": "flat", "stage": "mask_pack"}) > 0


def test_busy_share_is_the_workers_wall_less_its_two_waits(ctx):
    series = "weaviate_tpu_dispatch_stage_seconds_sum"
    wall = delta(ctx, series, {"stage": "worker_wall"})
    waits = (delta(ctx, series, {"stage": "idle"})
             + delta(ctx, series, {"stage": "slot_wait"}))
    busy = run.read_layer_metric("dispatch_busy_pct", ctx)
    assert busy == pytest.approx(100.0 * (1.0 - waits / wall))
    assert 0.0 < busy < 100.0
    # one worker thread: its sides' walls cannot exceed the wall clock,
    # which every request's residency, end to end, bounds from above
    assert wall < delta(ctx, "weaviate_tpu_request_stage_seconds_sum",
                        {"stage": "server_residency"})


def test_a_stage_that_never_ran_reads_none(ctx):
    assert run.read_layer_metric("rescore_ms", ctx) is None  # no BQ here


@pytest.mark.parametrize("name", NEW)
def test_on_a_parent_without_the_families_every_new_reader_reads_none(name):
    parent = {"before": page("scrape_filtered_before.prom", True),
              "after": page("scrape_filtered_after.prom", True),
              "mix": {"filter": None}}
    assert run.read_layer_metric(name, parent) is None
    # the accepted readers still read on the same pages
    assert run.read_layer_metric("host_ms", parent) > 0
