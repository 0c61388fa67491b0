"""The eight per-layer readers of PR 39 (the interpreter's account). On
the recorded scrapes of a program without the account (PR 25's pages, as
a parent serves) every one returns None and raises nothing; on two
stated pages that hold the new series each reads the arithmetic its
docstring gives (counts and host-clock seconds: no device number)."""

import json
import os

import pytest

import run
import wire

HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("interpreter_wait_ms", "request_off_cpu_ms", "cpu_ms_per_search",
       "edge_cpu_ms", "pool_cpu_ms", "serve_thread_busy_pct",
       "dispatch_cpu_ms", "dispatch_off_cpu_ms")

CPU = "weaviate_tpu_thread_cpu_seconds_total"
STAGE = "weaviate_tpu_request_stage_seconds"
PHASE = "weaviate_tpu_request_phase_seconds"
WALL = "weaviate_tpu_dispatch_stage_seconds"
DCPU = "weaviate_tpu_dispatch_stage_cpu_seconds"
PROBE = "weaviate_tpu_interpreter_wait_seconds"
SEARCH = 'operation="grpc.search"'


def stated(scale: float) -> str:
    """A page after ``scale`` windows of: 10 s of wall, 1,000 Searches in
    200 dispatches, 500 probe samples."""
    roles = {"grpc_serve": 4.0, "grpc_core": 1.0, "grpc_pool": 3.0,
             "batcher_worker": 0.6, "batcher_drain": 0.4,
             "python_other": 0.25, "exited": 0.75}
    stages = {  # wall, cpu: seconds over the window's 200 sides
        "assemble": (0.9, 0.1), "mask_pack": (0.3, 0.3),
        "deliver": (0.2, 0.1), "finish": (0.5, 0.2),
        "d2h_wait": (2.0, 0.1), "idle": (5.0, 0.0)}
    stamped = 50  # sides that took CPU stamps: one in four
    lines = [f"weaviate_tpu_scrape_clock_seconds {5000.0 + 10.0 * scale}"]
    lines += [f'{CPU}{{role="{r}"}} {v * scale}' for r, v in roles.items()]
    lines += [f'{PROBE}_sum {1.5 * scale}', f'{PROBE}_count {500 * scale}',
              f'{STAGE}_sum{{{SEARCH},stage="off_cpu"}} {40.0 * scale}',
              f'{STAGE}_count{{{SEARCH},stage="off_cpu"}} {1000 * scale}',
              f'{PHASE}_count{{{SEARCH},phase="queue_wait",collection="Sift"'
              f',tenant="-"}} {1000 * scale}',
              f'{PHASE}_count{{{SEARCH},phase="host",collection="Sift"'
              f',tenant="-"}} {1000 * scale}',
              'weaviate_tpu_query_batcher_compile_bucket_total'
              f'{{b="8",k="16"}} {150 * scale}',
              'weaviate_tpu_query_batcher_compile_bucket_total'
              f'{{b="4",k="16"}} {50 * scale}']
    for stage, (wall, cpu) in stages.items():
        labels = f'{{kind="flat",stage="{stage}"}}'
        lines += [f"{WALL}_sum{labels} {wall * scale}",
                  f"{WALL}_count{labels} {200 * scale}",
                  f"{DCPU}_sum{labels} {cpu * scale * stamped / 200}",
                  f"{DCPU}_count{labels} {stamped * scale}"]
    return "\n".join(lines) + "\n"


@pytest.fixture(scope="module")
def ctx():
    return {"before": wire.Prom(stated(1.0)), "after": wire.Prom(stated(2.0)),
            "mix": {"filter": None}}


@pytest.fixture(scope="module")
def parent_ctx():
    def page(name):
        with open(os.path.join(HERE, "recorded", name)) as f:
            return wire.Prom(f.read())
    return {"before": page("scrape_filtered_before.prom"),
            "after": page("scrape_filtered_after.prom"),
            "mix": {"filter": {"property": "bucket"}}}


@pytest.mark.parametrize("name", NEW)
def test_every_new_metric_is_declared_last_with_a_reader(name):
    with open(os.path.join(run.REPO, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert [m["name"] for m in per_layer[-len(NEW):]] == list(NEW)
    entry = next(m for m in per_layer if m["name"] == name)
    assert "workloads" not in entry          # every cell reports it
    assert entry["better"] == "lower"
    base = os.path.join(run.HERE, "layer_metrics", name)
    assert os.path.exists(base + ".json") != os.path.exists(base + ".py")


@pytest.mark.parametrize("name", NEW)
def test_on_a_page_without_the_account_a_reader_returns_none(parent_ctx,
                                                             name):
    assert run.read_layer_metric(name, parent_ctx) is None


@pytest.mark.parametrize("name", NEW)
def test_on_an_idle_window_a_reader_returns_none(name):
    page = wire.Prom(stated(1.0))
    idle = {"before": page, "after": page, "mix": {"filter": None}}
    assert run.read_layer_metric(name, idle) is None


@pytest.mark.parametrize("name,want", [
    ("interpreter_wait_ms", 1.5 / 500 * 1000.0),
    ("request_off_cpu_ms", 40.0 / 1000 * 1000.0),
    ("cpu_ms_per_search", 10.0 / 1000 * 1000.0),        # every role
    ("edge_cpu_ms", (4.0 + 1.0) / 1000 * 1000.0),       # serve + core
    ("pool_cpu_ms", 3.0 / 1000 * 1000.0),
    ("serve_thread_busy_pct", 100.0 * 4.0 / 10.0),
    ("dispatch_cpu_ms", (0.6 + 0.4) / 200 * 1000.0),    # worker + drain
    # assemble 0.8 + mask_pack 0 + deliver 0.1 + finish 0.3, not the
    # d2h wait's 1.9 and not idle's 5: those wait by design
    ("dispatch_off_cpu_ms", 1.2 / 200 * 1000.0),
])
def test_a_reader_reads_the_arithmetic_of_its_docstring(ctx, name, want):
    assert run.read_layer_metric(name, ctx) == pytest.approx(want)


def _dispatch_pages(kinds, dispatches=200):
    """Two pages a window apart: ``kinds`` = {kind: (sides, stamped,
    assemble wall s, assemble CPU s over the stamped sides)}."""
    def page(scale):
        lines = ['weaviate_tpu_query_batcher_compile_bucket_total'
                 f'{{b="8",k="16"}} {dispatches * scale}']
        for kind, (sides, stamped, wall, cpu) in kinds.items():
            labels = f'{{kind="{kind}",stage="assemble"}}'
            lines += [f"{WALL}_sum{labels} {wall * scale}",
                      f"{WALL}_count{labels} {sides * scale}",
                      f"{DCPU}_sum{labels} {cpu * scale}",
                      f"{DCPU}_count{labels} {stamped * scale}"]
        return wire.Prom("\n".join(lines) + "\n")
    return {"before": page(1.0), "after": page(2.0), "mix": {"filter": None}}


def test_dispatch_off_cpu_scales_every_kind_by_its_own_counts():
    """Two kinds on one thread, stamped at different shares: one scale
    over their summed counts would read (1.0 + 0.2) - 0.5 * 300 / 60."""
    pages = _dispatch_pages({"flat": (200, 50, 1.0, 0.2),      # x 4
                             "flat.solo": (100, 10, 0.2, 0.01)})  # x 10
    want = (1.0 - 0.2 * 4) + (0.2 - 0.01 * 10)
    assert run.read_layer_metric("dispatch_off_cpu_ms", pages) \
        == pytest.approx(want / 200 * 1000.0)


def test_dispatch_off_cpu_has_no_reading_under_a_few_stamped_sides():
    assert run.read_layer_metric("dispatch_off_cpu_ms", _dispatch_pages(
        {"flat": (28, 7, 1.0, 0.2)})) is None
    assert run.read_layer_metric("dispatch_off_cpu_ms", _dispatch_pages(
        {"flat": (32, 8, 1.0, 0.2)})) == pytest.approx(
            (1.0 - 0.2 * 4) / 200 * 1000.0)


def test_dispatch_off_cpu_shows_a_scaled_cpu_that_passes_its_wall():
    """Ticks on few sides: 0.16 s scaled against 0.154 s of wall reads
    below 0, and is not cut."""
    assert run.read_layer_metric("dispatch_off_cpu_ms", _dispatch_pages(
        {"flat": (800, 200, 0.154, 0.04)})) == pytest.approx(
            (0.154 - 0.16) / 200 * 1000.0)


def test_the_parts_are_inside_the_whole(ctx):
    whole = run.read_layer_metric("cpu_ms_per_search", ctx)
    parts = sum(run.read_layer_metric(n, ctx)
                for n in ("edge_cpu_ms", "pool_cpu_ms"))
    dispatch = run.read_layer_metric("dispatch_cpu_ms", ctx) * 200 / 1000
    assert parts + dispatch < whole
