"""Prometheus text-exposition correctness: escaping, histogram lines,
scrape-vs-writer concurrency (ISSUE 2 satellites), the OpenMetrics
flavor with exemplars, and the label-cardinality guard (ISSUE 15)."""

import re
import threading
import time

import pytest

from weaviate_tpu.runtime.metrics import (MetricsRegistry,
                                          escape_label_value)


# -- strict text-format parser (ISSUE 15 satellite) ---------------------------
#
# A hand-rolled exposition needs a hand-rolled conformance check: this
# parser applies the text-format grammar strictly (escaping, label
# syntax, exemplar syntax, `# EOF` tolerated) so an exposition
# regression fails tier-1 instead of silently breaking scrapes.


def _parse_label_block(s: str, i: int) -> tuple[dict, int]:
    """Parse ``{name="value",...}`` starting at ``s[i] == '{'``; returns
    (labels, index after '}'). Applies the escaping rules — raises on
    any malformation."""
    assert s[i] == "{", s[i:]
    i += 1
    labels: dict[str, str] = {}
    while s[i] != "}":
        j = s.index("=", i)
        name = s[i:j]
        assert re.fullmatch(r"[a-zA-Z_][\w]*", name), name
        assert s[j + 1] == '"', s[j:]
        k = j + 2
        buf = []
        while True:
            c = s[k]
            if c == "\\":
                nxt = s[k + 1]
                assert nxt in ('\\', '"', 'n'), f"bad escape \\{nxt}"
                buf.append({"\\": "\\", '"': '"', "n": "\n"}[nxt])
                k += 2
            elif c == '"':
                k += 1
                break
            else:
                assert c != "\n"
                buf.append(c)
                k += 1
        labels[name] = "".join(buf)
        if s[k] == ",":
            k += 1
        i = k
    return labels, i + 1


def parse_openmetrics(text: str) -> dict:
    """Strict parse of the (OpenMetrics-flavored) exposition: returns
    ``{"types": {family: type}, "samples": [{name, labels, value,
    exemplar}]}``; ``exemplar`` is ``{"labels", "value", "ts"}`` or
    None. Tolerates (and validates the placement of) ``# EOF``."""
    types: dict[str, str] = {}
    samples: list[dict] = []
    lines = text.splitlines()
    for n, ln in enumerate(lines):
        if not ln:
            continue
        if ln == "# EOF":
            assert n == len(lines) - 1, "# EOF must terminate the stream"
            continue
        if ln.startswith("# TYPE "):
            _, _, rest = ln.partition("# TYPE ")
            fam, _, kind = rest.partition(" ")
            assert kind in ("counter", "gauge", "histogram", "summary",
                            "untyped"), kind
            types[fam] = kind
            continue
        if ln.startswith("# HELP "):
            continue
        assert not ln.startswith("#"), f"unknown comment line {ln!r}"
        m = re.match(r"[a-zA-Z_:][\w:]*", ln)
        assert m, ln
        name = m.group(0)
        i = m.end()
        labels: dict[str, str] = {}
        if i < len(ln) and ln[i] == "{":
            labels, i = _parse_label_block(ln, i)
        assert ln[i] == " ", ln
        rest = ln[i + 1:]
        exemplar = None
        if " # " in rest:
            value_str, _, ex = rest.partition(" # ")
            ex_labels, j = _parse_label_block(ex, 0)
            ex_fields = ex[j:].split()
            assert len(ex_fields) in (1, 2), ex
            exemplar = {"labels": ex_labels,
                        "value": float(ex_fields[0]),
                        "ts": float(ex_fields[1])
                        if len(ex_fields) == 2 else None}
        else:
            value_str = rest
        assert " " not in value_str, ln
        samples.append({"name": name, "labels": labels,
                        "value": float(value_str), "exemplar": exemplar})
    return {"types": types, "samples": samples}


def _unescape(v: str) -> str:
    """Inverse of the text-format label escaping (what a Prometheus
    parser applies)."""
    out, i = [], 0
    while i < len(v):
        c = v[i]
        if c == "\\" and i + 1 < len(v):
            n = v[i + 1]
            if n == "\\":
                out.append("\\")
            elif n == '"':
                out.append('"')
            elif n == "n":
                out.append("\n")
            else:
                out.append(c + n)
            i += 2
        else:
            out.append(c)
            i += 1
    return "".join(out)


def test_label_escaping_round_trip():
    nasty = 'a"b\\c\nd'
    escaped = escape_label_value(nasty)
    assert "\n" not in escaped  # a raw newline would corrupt the scrape
    assert _unescape(escaped) == nasty

    reg = MetricsRegistry()
    c = reg.counter("objs", "objects", ("collection",))
    c.labels(nasty).inc(2)
    text = reg.expose()
    # one sample line, no stray lines from the embedded newline
    sample_lines = [ln for ln in text.splitlines()
                    if ln.startswith("objs{")]
    assert len(sample_lines) == 1
    m = re.match(r'objs\{collection="(.*)"\} 2\.0', sample_lines[0])
    assert m, sample_lines[0]
    assert _unescape(m.group(1)) == nasty


def test_help_escaping():
    reg = MetricsRegistry()
    reg.counter("c", "line one\nline two \\ backslash").inc()
    help_lines = [ln for ln in reg.expose().splitlines()
                  if ln.startswith("# HELP c ")]
    assert help_lines == ["# HELP c line one\\nline two \\\\ backslash"]


def test_histogram_exposition_lines():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency", ("op",), buckets=(0.1, 1.0))
    child = h.labels('scan"fast')
    child.observe(0.05)
    child.observe(0.5)
    child.observe(5.0)
    text = reg.expose()
    esc = escape_label_value('scan"fast')
    assert f'lat_bucket{{op="{esc}",le="0.1"}} 1' in text
    assert f'lat_bucket{{op="{esc}",le="1.0"}} 2' in text
    assert f'lat_bucket{{op="{esc}",le="+Inf"}} 3' in text
    assert f'lat_count{{op="{esc}"}} 3' in text
    sum_line = [ln for ln in text.splitlines()
                if ln.startswith(f'lat_sum{{op="{esc}"}}')]
    assert len(sum_line) == 1
    assert abs(float(sum_line[0].rsplit(" ", 1)[1]) - 5.55) < 1e-9


def test_concurrent_labels_vs_expose():
    """labels() inserts racing expose() iteration must neither raise nor
    emit malformed lines."""
    reg = MetricsRegistry()
    c = reg.counter("ops", "ops", ("who",))
    stop = threading.Event()
    errors = []

    def writer(n):
        i = 0
        while not stop.is_set():
            try:
                c.labels(f"w{n}-{i % 50}").inc()
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return
            i += 1

    threads = [threading.Thread(target=writer, args=(n,))
               for n in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(50):
            text = reg.expose()
            for ln in text.splitlines():
                if ln.startswith("#") or not ln:
                    continue
                assert re.match(r'^[a-zA-Z_:][\w:]*(\{.*\})? \S+$', ln), ln
    finally:
        stop.set()
        for t in threads:
            t.join(5)
    assert not errors


def test_rest_metrics_endpoint_serves_text(tmp_path):
    import urllib.request

    from weaviate_tpu.api.rest import RestServer
    from weaviate_tpu.db.database import Database

    db = Database(str(tmp_path))
    srv = RestServer(db)
    srv.start()
    try:
        resp = urllib.request.urlopen(f"http://{srv.address}/v1/metrics")
        ctype = resp.headers.get("Content-Type", "")
        assert ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype
        body = resp.read().decode()
        assert "# TYPE weaviate_tpu_query_duration_seconds histogram" \
            in body
    finally:
        srv.stop()
        db.close()


def test_exposition_carries_the_stage_families_and_not_the_old_three():
    """ISSUE 25: the two stage families are exposed with their label
    sets; the batcher's three per-request histograms, whose stamps the
    phase and span series already carried, are gone."""
    from weaviate_tpu.runtime import tailboard
    from weaviate_tpu.runtime.metrics import (dispatch_stage_seconds,
                                              registry,
                                              request_stage_seconds)

    assert request_stage_seconds.label_names == ("operation", "stage")
    assert dispatch_stage_seconds.label_names == ("kind", "stage")
    t = time.perf_counter()
    with tailboard.request("grpc.search", t_entry=t, t_arrival=t):
        tailboard.mark("parse")
    rec = tailboard.new_dispatch("batcher", "flat")
    tailboard.bind_dispatch(rec, "worker")
    with tailboard.dispatch_stage("launch"):
        pass
    tailboard.unbind_dispatch()
    tailboard.flush()
    body = registry.expose()
    parsed = parse_openmetrics(body)
    assert parsed["types"]["weaviate_tpu_request_stage_seconds"] == \
        parsed["types"]["weaviate_tpu_dispatch_stage_seconds"] == \
        "histogram"
    seen = {(x["name"], tuple(sorted(x["labels"].items())))
            for x in parsed["samples"]}
    assert ("weaviate_tpu_request_stage_seconds_count",
            (("operation", "grpc.search"), ("stage", "pool_wait"))) in seen
    assert ("weaviate_tpu_dispatch_stage_seconds_sum",
            (("kind", "flat"), ("stage", "launch"))) in seen
    for gone in ("wait", "execute", "transfer"):
        assert f"weaviate_tpu_query_batcher_{gone}_seconds" not in body


def test_machine_id_persists_across_boots(tmp_path):
    from weaviate_tpu.runtime.telemetry import Telemeter

    class _Db:
        def list_collections(self):
            return []

    t1 = Telemeter(_Db(), data_dir=str(tmp_path))
    t2 = Telemeter(_Db(), data_dir=str(tmp_path))
    assert t1.machine_id == t2.machine_id
    assert (tmp_path / "machine_id").read_text().strip() == t1.machine_id
    # no data dir -> ephemeral, but still a valid uuid-ish string
    t3 = Telemeter(_Db())
    assert t3.machine_id and t3.machine_id != t1.machine_id


# -- metrics hygiene lint (tools/lint_metrics.py, ISSUE 4 satellite) ----------


def _load_lint():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "lint_metrics.py")
    spec = importlib.util.spec_from_file_location("lint_metrics", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_registered_metrics_pass_lint():
    """Every metric in the process registry has HELP text, snake_case
    weaviate_tpu_-prefixed naming, and shows up in the exposition —
    importing the runtime (and the modules that registered extra vecs in
    this test process) first so the full live set is linted."""
    import weaviate_tpu.runtime  # noqa: F401 — registers the standard set

    lint = _load_lint()
    assert lint.lint() == []


def test_lint_catches_violations():
    lint = _load_lint()
    reg = MetricsRegistry()
    reg.counter("weaviate_tpu_ok_total", "has help")
    reg.counter("weaviate_tpu_no_help_total", "")
    reg.gauge("camelCaseName", "bad name")
    reg.gauge("weaviate_tpu_bad_label", "help", ("badLabel",))
    problems = lint.lint(reg)
    assert any("no_help_total" in p and "HELP" in p for p in problems)
    assert any("camelCaseName" in p for p in problems)
    assert any("badLabel" in p for p in problems)
    assert not any("weaviate_tpu_ok_total" in p for p in problems)


def test_lint_flags_descending_buckets():
    lint = _load_lint()
    reg = MetricsRegistry()
    reg.histogram("weaviate_tpu_bad_buckets_seconds", "help",
                  buckets=(1.0, 0.5, 2.0))
    problems = lint.lint(reg)
    assert any("ascending" in p for p in problems)


# -- OpenMetrics exemplars (ISSUE 15) -----------------------------------------


def test_openmetrics_exemplars_round_trip():
    """Exemplar-carrying histogram buckets pass the strict parser —
    including a trace id that needs label escaping — and the plain
    (0.0.4) exposition stays exemplar-free for old scrapers."""
    reg = MetricsRegistry()
    h = reg.histogram("weaviate_tpu_phase_seconds", "phases", ("op",),
                      buckets=(0.1, 1.0))
    h.labels("q").observe(0.05, exemplar={"trace_id": "abc123"})
    h.labels("q").observe(0.5, exemplar={"trace_id": 'we"ird\nid'})
    h.labels("q").observe(7.0)  # no exemplar on this one
    om = reg.expose(openmetrics=True)
    assert om.rstrip("\n").endswith("# EOF")
    parsed = parse_openmetrics(om)
    assert parsed["types"]["weaviate_tpu_phase_seconds"] == "histogram"
    buckets = [s for s in parsed["samples"]
               if s["name"] == "weaviate_tpu_phase_seconds_bucket"]
    by_le = {s["labels"]["le"]: s for s in buckets}
    assert by_le["0.1"]["exemplar"]["labels"]["trace_id"] == "abc123"
    assert by_le["0.1"]["exemplar"]["value"] == 0.05
    # the nastier exemplar landed on the 1.0 bucket, unescaped cleanly
    assert by_le["1.0"]["exemplar"]["labels"]["trace_id"] == 'we"ird\nid'
    # +Inf carries the LAST exemplar observed (every observation fits)
    assert by_le["+Inf"]["exemplar"] is not None
    # bucket counts stay cumulative/monotone under the parser's eye
    assert (by_le["0.1"]["value"] <= by_le["1.0"]["value"]
            <= by_le["+Inf"]["value"] == 3)
    # plain text format: same registry, not one exemplar
    plain = reg.expose()
    assert " # {" not in plain and "# EOF" not in plain
    parse_openmetrics(plain)  # and still strictly well-formed


def test_rest_metrics_openmetrics_negotiation(tmp_path):
    """/v1/metrics serves the OpenMetrics flavor on Accept (or
    ?format=openmetrics) and the whole live exposition passes the
    strict parser."""
    import urllib.request

    from weaviate_tpu.api.rest import RestServer
    from weaviate_tpu.db.database import Database
    from weaviate_tpu.runtime.metrics import request_phase_seconds

    request_phase_seconds.labels("objects", "host", "-", "-").observe(
        0.003, exemplar={"trace_id": "deadbeef"})
    db = Database(str(tmp_path))
    srv = RestServer(db)
    srv.start()
    try:
        req = urllib.request.Request(
            f"http://{srv.address}/v1/metrics",
            headers={"Accept": "application/openmetrics-text"})
        resp = urllib.request.urlopen(req)
        assert resp.headers["Content-Type"].startswith(
            "application/openmetrics-text")
        body = resp.read().decode()
        parsed = parse_openmetrics(body)  # strict: escaping + exemplars
        assert body.rstrip("\n").endswith("# EOF")
        assert any(s["exemplar"] is not None for s in parsed["samples"]
                   if s["name"] ==
                   "weaviate_tpu_request_phase_seconds_bucket")
        # OpenMetrics reserves the _total suffix: counter FAMILIES must
        # drop it (samples keep it) or a strict OM scraper rejects the
        # whole exposition
        for fam, kind in parsed["types"].items():
            assert not (kind == "counter" and fam.endswith("_total")), fam
        assert parsed["types"].get("weaviate_tpu_objects") == "counter"
        # param fallback for curl-without-headers use
        resp2 = urllib.request.urlopen(
            f"http://{srv.address}/v1/metrics?format=openmetrics")
        assert resp2.read().decode().rstrip("\n").endswith("# EOF")
        # default stays 0.0.4 text (no exemplars, no EOF)
        resp3 = urllib.request.urlopen(f"http://{srv.address}/v1/metrics")
        assert resp3.headers["Content-Type"].startswith("text/plain")
        assert "# EOF" not in resp3.read().decode()
    finally:
        srv.stop()
        db.close()


# -- label-cardinality guard (ISSUE 15 satellite) -----------------------------


@pytest.fixture
def small_series_cap(monkeypatch):
    from weaviate_tpu.runtime import metrics as m

    monkeypatch.setenv("WEAVIATE_TPU_METRIC_MAX_SERIES", "3")
    m.reset_series_cap_for_tests()
    yield
    m.reset_series_cap_for_tests()


def test_series_cap_overflows_to_other(small_series_cap):
    from weaviate_tpu.runtime.metrics import metric_series_dropped

    reg = MetricsRegistry()
    c = reg.counter("weaviate_tpu_caps_total", "capped", ("tenant",))
    for t in ("a", "b", "c"):
        c.labels(t).inc()
    before = metric_series_dropped.labels("weaviate_tpu_caps_total").value
    c.labels("d").inc()      # over the cap: redirected
    c.labels("e").inc(2)     # same
    text = reg.expose()
    assert 'weaviate_tpu_caps_total{tenant="a"} 1.0' in text
    # the overflow series absorbed both redirected label sets
    assert 'weaviate_tpu_caps_total{tenant="other"} 3.0' in text
    assert 'tenant="d"' not in text and 'tenant="e"' not in text
    dropped = metric_series_dropped.labels("weaviate_tpu_caps_total").value
    assert dropped - before == 2
    # established series keep updating without counting as drops
    c.labels("a").inc()
    assert 'weaviate_tpu_caps_total{tenant="a"} 2.0' in reg.expose()


def test_series_cap_ignores_unlabeled_metrics(small_series_cap):
    reg = MetricsRegistry()
    g = reg.gauge("weaviate_tpu_plain", "no labels")
    g.set(5.0)  # must not trip the guard machinery
    assert "weaviate_tpu_plain 5.0" in reg.expose()
