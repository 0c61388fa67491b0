"""G5 metrics-conventions: Prometheus hygiene at the registration site,
plus timing-metric unit conventions.

The lint_metrics seed (PR 4) checks the LIVE registry — right for the
exposition-presence rule, but it only sees metrics whatever process
imported. The static half rides the graftlint driver instead: every
``registry.counter/gauge/histogram("name", "help", (labels,))`` call
with literal arguments is checked for snake_case ``weaviate_tpu_``
naming, non-empty HELP, and snake_case labels — so a camelCase metric
in a module no test imports still fails the gate. Non-literal
registrations (the registry's own internals, dynamic names) are skipped,
not guessed at; the runtime lint still covers those.

Timing conventions (fields are compared by NAME across runs and
traces, so an ambiguous unit is a silent 1000x comparison error):

- a registered metric whose name says it measures time (``*duration*``,
  ``*latency*``, ``*elapsed*``) must state its unit — a ``_seconds`` /
  ``_ms`` / ``_us`` / ``_ns`` name suffix, or an explicit unit word in
  the HELP text;
- trace timing FIELDS (dict keys, ``sp.set(...)`` attrs) must
  not use ambiguous or nonstandard unit suffixes: ``wall_s`` /
  ``device_seconds`` / ``host_time`` etc. are flagged — the repo
  convention is ``*_ms``;
- device-attributed timings are named exactly ``device_ms`` (that is
  the field tracing.device_sync emits and ``runtime/bands`` reports as
  a section's noise) — aliases like ``dev_ms`` / ``device_time_ms``
  fork the schema.

``lint(registry)`` below is the runtime half, kept verbatim from
tools/lint_metrics.py so that file can become a thin shim without
changing tests/test_metrics_exposition.py.
"""

from __future__ import annotations

import ast
import re

from tools.graftlint.core import Checker, FileContext, Violation

_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
_PREFIX = "weaviate_tpu_"
_REGISTER_METHODS = ("counter", "gauge", "histogram", "summary")

# -- timing conventions -------------------------------------------------------

#: a metric NAME that claims to measure time
_TIMEY_NAME_RE = re.compile(r"(duration|latency|elapsed)")
#: unit-stating name suffixes accepted for timing metrics
_UNIT_SUFFIX_RE = re.compile(r"_(seconds|ms|us|ns|minutes)$")
#: unit words accepted in HELP text when the name carries no suffix
_UNIT_HELP_RE = re.compile(
    r"\b(seconds|milliseconds|microseconds|nanoseconds|ms|us|ns)\b",
    re.IGNORECASE)
#: bench/trace timing fields with an ambiguous or nonstandard unit
#: suffix — the repo convention is ``<what>_ms``
_AMBIG_FIELD_RE = re.compile(
    r"^(wall|host|device|e2e|elapsed|dispatch|fetch)"
    r"_(s|sec|secs|seconds|millis|milliseconds|time|duration)$")
#: device-attributed timing aliases that fork the ``device_ms`` schema
_DEVICE_ALIAS_RE = re.compile(r"^(dev_ms|device_time_ms|device_timing_ms)$")

# -- metering-counter conventions (ISSUE 17: kernelscope's per-tenant
#    device metering made these load-bearing — a time-accumulating
#    COUNTER is a meter, and meters are ``*_seconds_total``: seconds
#    because rate() math and the phase histograms are seconds repo-wide,
#    _total because Prometheus counters carry it and recording rules
#    key on the suffix) --------------------------------------------------------

#: a counter NAME that claims a time unit suffix. Two-letter unit
#: tokens (_us/_ns) are excluded on purpose: they collide with English
#: plurals (``other_ns_total`` is a namespace count, not nanoseconds)
_COUNTER_TIME_RE = re.compile(
    r"_(seconds|ms|milliseconds|microseconds|nanoseconds|minutes)"
    r"(_total)?$")
#: the ONE accepted shape for time-accumulating counters
_METER_COUNTER_RE = re.compile(r"_seconds_total$")

# -- histogram conventions (ISSUE 15: the phase histograms made these
#    load-bearing — ``le`` bucket bounds are SECONDS repo-wide, and the
#    OpenMetrics exemplar grammar is part of the scrape wire format) ----------

#: a TIMING histogram must be named ``*_seconds``: observe() feeds it
#: perf_counter deltas in seconds and the declared ``le`` bounds are
#: compared against those — a ``_ms`` (or unsuffixed) timing histogram
#: either lies about its unit or its buckets silently never match
_HISTOGRAM_SECONDS_RE = re.compile(r"_seconds$")

#: exemplar line grammar for the runtime lint: `` # {labels} value [ts]``
_EXEMPLAR_RE = re.compile(
    r' # \{[a-zA-Z_][\w]*="(?:[^"\\\n]|\\\\|\\"|\\n)*"'
    r'(?:,[a-zA-Z_][\w]*="(?:[^"\\\n]|\\\\|\\n|\\")*")*\} '
    r"\S+( \S+)?$")


# -- runtime lint (the lint_metrics seed, unchanged semantics) ----------------


def lint(registry=None) -> list[str]:
    """Returns a list of violation strings (empty = clean). Importing
    the runtime package is enough to register the full standard metric
    set — modules add their vecs at import time."""
    if registry is None:
        import weaviate_tpu.runtime  # registers the standard set  # noqa: F401
        from weaviate_tpu.runtime.metrics import registry as registry

    problems: list[str] = []
    with registry._lock:
        metrics = dict(registry._metrics)
    exposition = registry.expose()
    for name, m in sorted(metrics.items()):
        if not m.help or not str(m.help).strip():
            problems.append(f"{name}: missing HELP text")
        if not _NAME_RE.match(name):
            problems.append(f"{name}: not snake_case")
        if not name.startswith(_PREFIX):
            problems.append(f"{name}: missing {_PREFIX!r} prefix")
        for ln in m.label_names:
            if not _NAME_RE.match(ln):
                problems.append(f"{name}: label {ln!r} not snake_case")
        if f"# HELP {name} " not in exposition \
                or f"# TYPE {name} " not in exposition:
            problems.append(f"{name}: absent from the text exposition")
        buckets = getattr(m, "buckets", None)
        if buckets is not None and list(buckets) != sorted(set(buckets)):
            problems.append(f"{name}: histogram buckets must be "
                            "strictly ascending")
    # OpenMetrics exemplar hygiene: every exemplar the registry renders
    # must match the `` # {labels} value [ts]`` grammar with escaped
    # label values — a malformed exemplar corrupts the whole scrape
    try:
        om = registry.expose(openmetrics=True)
    except TypeError:  # foreign registry without the openmetrics flavor
        om = ""
    for ln in om.splitlines():
        if ln.startswith("#") or " # {" not in ln:
            continue
        if not _EXEMPLAR_RE.search(ln):
            problems.append(f"malformed OpenMetrics exemplar: {ln!r}")
    return problems


# -- static checker -----------------------------------------------------------


class MetricsConventionChecker(Checker):
    id = "G5"
    name = "metrics-conventions"

    def applies_to(self, path: str) -> bool:
        # production modules (tests register throwaway metrics on
        # private registries on purpose and stay excluded)
        return path.endswith(".py") and path.startswith("weaviate_tpu/")

    def check(self, ctx: FileContext) -> list[Violation]:
        out: list[Violation] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _REGISTER_METHODS:
                out.extend(self._check_registration(ctx, node))
            out.extend(self._check_timing_fields(ctx, node))
        out.extend(self._check_explain_emissions(ctx))
        return out

    # -- explain-emission hygiene ---------------------------------------------
    #
    # kernelscope.explain_note() arguments are evaluated EAGERLY even
    # when no sink is installed (it's a plain call), and the collected
    # plan is JSON-serialized at the API edge. A device value passed as
    # an explain field is therefore a deferred host sync G1 cannot see
    # (the sync happens in json.dumps, outside the hot dirs). Piggyback
    # G1's taint machinery: in the dispatch-path modules, every
    # explain_note argument must already be a host scalar.

    _EXPLAIN_DIRS = ("weaviate_tpu/engine/", "weaviate_tpu/ops/",
                     "weaviate_tpu/parallel/")
    _EXPLAIN_FILES = ("weaviate_tpu/runtime/query_batcher.py",)

    def _check_explain_emissions(self, ctx) -> list[Violation]:
        if not (ctx.path in self._EXPLAIN_FILES
                or any(ctx.path.startswith(d) for d in self._EXPLAIN_DIRS)):
            return []
        from tools.graftlint.core import walk_shallow
        from tools.graftlint.g1_host_sync import _FunctionPass

        out: list[Violation] = []
        units: list[list[ast.stmt]] = []
        module_level = [s for s in ctx.tree.body
                        if not isinstance(s, (ast.FunctionDef,
                                              ast.AsyncFunctionDef,
                                              ast.ClassDef))]
        if module_level:
            units.append(module_level)
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                units.append(node.body)
        for body in units:
            fp = _FunctionPass(body)
            fp.propagate()
            # replay assignments in source order so each emission is
            # judged against the taint state at its own position (same
            # discipline as the G1 checker)
            events = []
            for node in walk_shallow(body):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "explain_note":
                    events.append((node.lineno, 0, node.col_offset,
                                   "note", node))
                if isinstance(node, (ast.Assign, ast.AnnAssign,
                                     ast.AugAssign, ast.NamedExpr)):
                    end = node.lineno if node.value is None else \
                        getattr(node.value, "end_lineno", node.lineno)
                    events.append((end, 1, node.col_offset,
                                   "assign", node))
            events.sort(key=lambda e: e[:3])
            for _, _, _, kind, node in events:
                if kind == "assign":
                    fp.apply_assign(node)
                    continue
                for val in list(node.args) + [kw.value
                                              for kw in node.keywords]:
                    if fp.is_device(val):
                        out.append(self._violation(
                            ctx, val,
                            "explain_note() argument is a device value "
                            "— explain fields are JSON-serialized at "
                            "the API edge, so this is a deferred "
                            "host sync G1 cannot see; pass host "
                            "scalars (lens, ints, precomputed "
                            "fractions) only"))
        return out

    # -- timing-field conventions ---------------------------------------------

    def _field_sites(self, node):
        """(key_string, anchor_node) pairs for the places bench/trace
        timing fields are born: dict literals, constant-key subscript
        assignments, and ``.set(...)``/``.update(...)`` keyword attrs."""
        if isinstance(node, ast.Dict):
            for key in node.keys:
                if isinstance(key, ast.Constant) \
                        and isinstance(key.value, str):
                    yield key.value, key
        elif isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Subscript) \
                        and isinstance(tgt.slice, ast.Constant) \
                        and isinstance(tgt.slice.value, str):
                    yield tgt.slice.value, tgt
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in ("set", "update"):
            for kw in node.keywords:
                if kw.arg:
                    yield kw.arg, kw.value

    def _check_timing_fields(self, ctx, node) -> list[Violation]:
        out = []
        for key, anchor in self._field_sites(node):
            if _DEVICE_ALIAS_RE.match(key):
                out.append(self._violation(
                    ctx, anchor,
                    f"device-attributed timing field {key!r} must be "
                    "named 'device_ms' — traces and band verdicts "
                    "read that exact field; an alias forks the schema"))
            elif _AMBIG_FIELD_RE.match(key):
                want = key.split("_", 1)[0] + "_ms"
                out.append(self._violation(
                    ctx, anchor,
                    f"timing field {key!r} has an ambiguous or "
                    f"nonstandard unit — name it {want!r} (repo "
                    "convention: timing fields state their unit as "
                    "_ms; an unstated unit is a silent 1000x "
                    "comparison error in the perf gate)"))
        return out

    def _violation(self, ctx, node, msg) -> Violation:
        return Violation(self.id, ctx.path, node.lineno, node.col_offset,
                         f"[metrics-conventions] {msg}")

    def _check_registration(self, ctx, call: ast.Call) -> list[Violation]:
        args = list(call.args)
        kwargs = {kw.arg: kw.value for kw in call.keywords}
        name_node = args[0] if args else kwargs.get("name")
        if not (isinstance(name_node, ast.Constant)
                and isinstance(name_node.value, str)):
            return []  # dynamic registration — runtime lint's job
        name = name_node.value
        out = []
        if not _NAME_RE.match(name):
            out.append(self._violation(
                ctx, name_node,
                f"metric {name!r} is not snake_case — Prometheus "
                "scrapers drop malformed families silently"))
        if not name.startswith(_PREFIX):
            out.append(self._violation(
                ctx, name_node,
                f"metric {name!r} missing the {_PREFIX!r} namespace "
                "prefix"))
        help_node = args[1] if len(args) > 1 else kwargs.get("help_text")
        if help_node is None or (isinstance(help_node, ast.Constant)
                                 and not str(help_node.value).strip()):
            out.append(self._violation(
                ctx, call,
                f"metric {name!r} registered without HELP text — a "
                "blank HELP is invisible until a dashboard goes blank"))
        if _TIMEY_NAME_RE.search(name) \
                and not _UNIT_SUFFIX_RE.search(name) \
                and call.func.attr != "histogram":
            # histograms get the STRICTER *_seconds rule below instead —
            # one finding per site, not two
            help_txt = (help_node.value
                        if isinstance(help_node, ast.Constant)
                        and isinstance(help_node.value, str) else "")
            if not _UNIT_HELP_RE.search(help_txt):
                out.append(self._violation(
                    ctx, name_node,
                    f"timing metric {name!r} states its unit nowhere — "
                    "suffix the name (_seconds/_ms/_us/_ns) or name "
                    "the unit in HELP; dashboards comparing unitless "
                    "timings are off by 1000x silently"))
        labels_node = (args[2] if len(args) > 2
                       else kwargs.get("label_names"))
        if isinstance(labels_node, (ast.Tuple, ast.List)):
            for el in labels_node.elts:
                if isinstance(el, ast.Constant) \
                        and isinstance(el.value, str) \
                        and not _NAME_RE.match(el.value):
                    out.append(self._violation(
                        ctx, el,
                        f"metric {name!r} label {el.value!r} is not "
                        "snake_case"))
        if call.func.attr == "histogram":
            out.extend(self._check_histogram(ctx, call, name, name_node,
                                             args, kwargs))
        if call.func.attr == "counter" \
                and _COUNTER_TIME_RE.search(name) \
                and not _METER_COUNTER_RE.search(name):
            out.append(self._violation(
                ctx, name_node,
                f"time-accumulating counter {name!r} must be named "
                "'*_seconds_total' — device/time meters are seconds "
                "repo-wide (rate() math, phase histograms) and "
                "Prometheus counters carry the _total suffix; a _ms "
                "meter or a missing _total forks the metering schema"))
        return out

    def _check_histogram(self, ctx, call: ast.Call, name: str, name_node,
                         args, kwargs) -> list[Violation]:
        """Histogram-only conventions: timing histograms are ``*_seconds``
        (``le`` bucket bounds are seconds repo-wide — observe() feeds
        perf_counter deltas), and literal bucket sets are declared
        strictly ascending (the child slots each observation by
        ``bisect_left`` over the declared tuple, so a misordered or
        duplicated bound lands observations in the wrong slot and the
        cumulative exposition miscounts silently)."""
        out = []
        if _TIMEY_NAME_RE.search(name) \
                and not _HISTOGRAM_SECONDS_RE.search(name):
            out.append(self._violation(
                ctx, name_node,
                f"timing histogram {name!r} must be named '*_seconds' — "
                "its le bucket bounds are seconds by repo convention "
                "(DEFAULT_BUCKETS, _Timer.observe); a _ms or unsuffixed "
                "timing histogram either lies about its unit or its "
                "buckets never match"))
        buckets_node = (args[3] if len(args) > 3 else kwargs.get("buckets"))
        if isinstance(buckets_node, (ast.Tuple, ast.List)):
            vals = []
            for el in buckets_node.elts:
                if isinstance(el, ast.Constant) \
                        and isinstance(el.value, (int, float)) \
                        and not isinstance(el.value, bool):
                    vals.append(float(el.value))
                else:
                    return out  # dynamic bucket expr — runtime lint's job
            if vals != sorted(set(vals)):
                out.append(self._violation(
                    ctx, buckets_node,
                    f"histogram {name!r} buckets must be declared "
                    "strictly ascending — a misordered or duplicated "
                    "bound miscounts observations and breaks le-based "
                    "quantile math in dashboards"))
        return out
