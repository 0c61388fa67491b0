"""graftlint framework tests (ISSUE 5).

Three layers:

1. per-checker fixtures — every checker G1-G5 is exercised against
   snippets with KNOWN positives and KNOWN negatives, so the contract
   of each invariant is pinned by tests, not by whatever the tree
   happens to contain;
2. mechanics — inline/file suppressions, baseline matching, stale-
   baseline detection, ``--update-baseline`` pruning, reason-required
   validation, per-file caching;
3. the whole-repo gate — ``weaviate_tpu/`` must produce ZERO
   non-baselined violations and zero stale baseline entries. Runs under
   tier-1 (pure AST: no device, no JAX import needed).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from tools.graftlint import core  # noqa: E402
from tools.graftlint.core import run  # noqa: E402


def write_tree(root, files: dict[str, str]):
    for rel, src in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    return str(root)


def lint_tree(root, files: dict[str, str], paths=None, **kwargs):
    """Write fixture files under ``root`` and run graftlint over them."""
    kwargs.setdefault("use_cache", False)
    return run(paths or list(files), write_tree(root, files), **kwargs)


def checks(res):
    return [(v.check, v.line) for v in res.violations]


# -- G1 host-sync -------------------------------------------------------------


G1_POSITIVE = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    def scan(q, x):
        d = jnp.sum(q * x, axis=1)
        jax.block_until_ready(d)            # P1: explicit sync
        host = np.asarray(d)                # P2: transfer of device value
        worst = float(d[0])                 # P3: scalar sync
        got = jax.device_get(d)             # P4: device_get
        n = d.sum().item()                  # P5: .item() on device chain
        return host, worst, got, n
"""

G1_NEGATIVE = """
    import numpy as np

    def ingest(rows, ids):
        rows = np.asarray(rows, dtype=np.float32)   # host -> host: fine
        m = float(rows[0, 0])                       # numpy scalar: fine
        k = int(ids.max())                          # numpy: fine
        return rows, m, k
"""


def test_g1_flags_sync_on_device_values(tmp_path):
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/engine/fixture.py": G1_POSITIVE})
    g1 = [v for v in res.violations if v.check == "G1"]
    assert len(g1) >= 4  # block_until_ready, asarray, float, device_get
    lines = {v.line for v in g1}
    assert {8, 9, 10, 11} <= lines


def test_g1_ignores_host_numpy(tmp_path):
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/engine/fixture.py": G1_NEGATIVE})
    assert [v for v in res.violations if v.check == "G1"] == []


def test_g1_scope_excludes_tracing_and_cold_paths(tmp_path):
    src = """
        import jax

        def device_sync(sp, *vals):
            jax.block_until_ready(vals)
    """
    res = lint_tree(tmp_path, {
        # the sanctioned sampled-sync site
        "weaviate_tpu/runtime/tracing.py": src,
        # same code outside the hot-path dirs: not G1's business
        "weaviate_tpu/api/rest_fixture.py": src,
    })
    assert [v for v in res.violations if v.check == "G1"] == []


def test_g1_taint_flows_through_assignment(tmp_path):
    src = """
        import jax.numpy as jnp
        import numpy as np

        def f(q):
            a = jnp.dot(q, q)
            b = a * 2 + 1
            c = b[0]
            return np.asarray(c)
    """
    res = lint_tree(tmp_path, {"weaviate_tpu/ops/fixture.py": src})
    assert [v.check for v in res.violations] == ["G1"]


def test_g1_boundary_kill_frees_downstream_host_reads(tmp_path):
    """One suppressed boundary transfer must be enough: after
    ``a = np.asarray(a)`` the name is host, so later float()/indexing
    need no bogus extra suppressions — while the boundary call itself
    still flags (here: unsuppressed, so exactly one G1)."""
    src = """
        import jax.numpy as jnp
        import numpy as np

        def f(q):
            a = jnp.dot(q, q)
            a = np.asarray(a)
            return float(a[0]) + float(a[1])
    """
    res = lint_tree(tmp_path, {"weaviate_tpu/ops/fixture.py": src})
    g1 = [v for v in res.violations if v.check == "G1"]
    assert len(g1) == 1 and g1[0].line == 7  # only the transfer itself


def test_g1_numpy_ufunc_on_device_value_is_a_sink(tmp_path):
    """np.sqrt(jnp_val) / np.where(dev_mask, ...) coerce the operand to
    host — same sync as asarray, must flag."""
    src = """
        import jax.numpy as jnp
        import numpy as np

        def f(x, a, b):
            y = np.sqrt(jnp.sum(x))
            mask = jnp.greater(x, 0)
            return y, np.where(mask, a, b)
    """
    res = lint_tree(tmp_path, {"weaviate_tpu/engine/fixture.py": src})
    g1 = [v for v in res.violations if v.check == "G1"]
    assert {v.line for v in g1} == {6, 8}


def test_g1_no_false_positive_before_first_device_assignment(tmp_path):
    """A name used for host values early and rebound to a device value
    LATER must not taint the earlier reads (straight-line order)."""
    src = """
        import jax.numpy as jnp
        import numpy as np

        def f(self, key, q):
            res = self.cache_lookup(key)
            if res is not None:
                return np.asarray(res)      # host branch: clean
            res = jnp.dot(q, q)
            return np.asarray(res)          # the real transfer: flags
    """
    res = lint_tree(tmp_path, {"weaviate_tpu/engine/fixture.py": src})
    g1 = [v for v in res.violations if v.check == "G1"]
    assert [v.line for v in g1] == [10]


def test_g1_loop_carried_taint_still_caught(tmp_path):
    """Device taint flowing around a loop back-edge (use textually
    before the device rebind) must still reach the sink."""
    src = """
        import jax.numpy as jnp
        import numpy as np

        def f(x, n):
            for _ in range(n):
                y = np.asarray(x)
                x = jnp.sin(x)
            return y
    """
    res = lint_tree(tmp_path, {"weaviate_tpu/engine/fixture.py": src})
    g1 = [v for v in res.violations if v.check == "G1"]
    assert [v.line for v in g1] == [7]


# -- G2 retrace-hazard --------------------------------------------------------


G2_POSITIVE = """
    import functools
    import jax

    STATICS = ("k",)

    @functools.partial(jax.jit, static_argnames=STATICS)
    def bad_statics(x, k):                      # P1: computed static set
        return x

    @functools.partial(jax.jit, static_argnames=("kk",))
    def typo(x, k):                             # P2: no param named kk
        return x

    @jax.jit
    def branchy(x):
        if x > 0:                               # P3: value branch on tracer
            return x
        return -x
"""

G2_NEGATIVE = """
    import functools
    import jax

    @functools.partial(jax.jit, static_argnames=("k", "metric"))
    def good(x, mask, k, metric):
        if k > 4 and metric == "dot":           # static args: fine
            x = x * 2
        if x.shape[0] > 8:                      # shape: static under trace
            x = x[:8]
        if mask is None:                        # identity vs None: fine
            return x
        return x * mask
"""


def test_g2_flags_retrace_hazards(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/ops/fixture.py": G2_POSITIVE})
    g2 = [v for v in res.violations if v.check == "G2"]
    msgs = " | ".join(v.message for v in g2)
    assert len(g2) == 3
    assert "literal" in msgs            # computed static_argnames
    assert "'kk'" in msgs               # typo'd static name
    assert "VALUE of traced argument" in msgs


def test_g2_accepts_static_shape_and_none_branches(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/ops/fixture.py": G2_NEGATIVE})
    assert [v for v in res.violations if v.check == "G2"] == []


# -- G3 pallas-invariants -----------------------------------------------------


G3_POSITIVE = """
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def masked_scan(q, x, allow_bits, tile_n: int = 384):   # P1: 384 % 512
        return q

    def kernel_loop(q_ref, n_ref, out_ref):
        for i in range(n_ref[0]):                           # P2: traced loop
            out_ref[i] = q_ref[i]

    def big_scratch(q, x):
        return pl.pallas_call(
            kernel_loop,
            grid=(1,),
            scratch_shapes=[pltpu.VMEM((2048, 2048), jnp.float32)],  # P3: 16MB
        )(q, x)
"""

G3_NEGATIVE = """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def plain_scan(q, x, tile_n: int = 512):        # lane-aligned default
        return q

    def masked_scan(q, x, allow_bits, tile_n: int = 1024):  # 1024 % 512 == 0
        return q

    def kernel(q_ref, x_ref, out_ref):
        for j in range(32):                          # literal bound: fine
            out_ref[:] = q_ref[:] + j
        nb = 4
        for i in range(nb):                          # local static: fine
            out_ref[:] = x_ref[:] * i

    def small_scratch(q):
        return pl.pallas_call(
            kernel,
            grid=(1,),
            scratch_shapes=[pltpu.VMEM((256, 128), jnp.float32)],
        )(q, q)
"""


def test_g3_flags_pallas_invariants(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/ops/fixture.py": G3_POSITIVE})
    g3 = [v for v in res.violations if v.check == "G3"]
    msgs = " | ".join(v.message for v in g3)
    assert len(g3) == 3
    assert "not a multiple of 512" in msgs
    assert "for-loop over a traced value" in msgs
    assert "exceeds" in msgs and "VMEM" in msgs


def test_g3_accepts_aligned_tiles_and_static_loops(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/ops/fixture.py": G3_NEGATIVE})
    assert [v for v in res.violations if v.check == "G3"] == []


# -- G4 lock-discipline -------------------------------------------------------


G4_POSITIVE = """
    import threading

    class Store:
        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0            # __init__: exempt

        def add(self, n):
            with self._lock:
                self._count += n

        def reset_unlocked(self):
            self._count = 0            # P1: write outside the lock

        def grow(self, n):
            if n > 0:
                self._cap = n          # P2: nested-statement write
"""

G4_NEGATIVE = """
    import threading

    class Store:
        def __init__(self):
            self._lock = threading.Lock()
            self._cv = threading.Condition(self._lock)
            self._count = 0

        def add(self, n):
            with self._lock:
                self._count += n

        def add_cv(self, n):
            with self._cv:             # Condition aliases the same lock
                self._count += n

        def _grow(self, n):
            \"\"\"Caller holds ``_lock``.\"\"\"
            self._count = n

        def rename(self, s):
            self.title = s             # public attr: out of G4's scope
"""

G4_ABBA_A = """
    import threading

    class Alpha:
        def __init__(self, beta):
            self._lock = threading.Lock()
            self._beta = beta

        def ping(self):
            with self._lock:
                self._beta.poke()

        def poke_back(self):
            with self._lock:
                pass
"""

G4_ABBA_B = """
    import threading

    class Beta:
        def __init__(self, alpha):
            self._lock = threading.Lock()
            self._alpha = alpha

        def poke(self):
            with self._lock:
                pass

        def pong(self):
            with self._lock:
                self._alpha.poke_back()
"""


def test_g4_flags_unlocked_underscore_writes(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/runtime/fx.py": G4_POSITIVE})
    g4 = [v for v in res.violations if v.check == "G4"]
    assert len(g4) == 2
    assert {"_count", "_cap"} == {v.message.split("self.")[1].split(" ")[0]
                                  for v in g4}


def test_g4_accepts_locked_cv_and_caller_holds(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/runtime/fx.py": G4_NEGATIVE})
    assert [v for v in res.violations if v.check == "G4"] == []


def test_g4_cross_module_lock_order_inversion(tmp_path):
    res = lint_tree(tmp_path, {
        "weaviate_tpu/runtime/alpha.py": G4_ABBA_A,
        "weaviate_tpu/runtime/beta.py": G4_ABBA_B,
    })
    cyc = [v for v in res.violations if "inversion" in v.message]
    assert len(cyc) == 1
    assert "Alpha._lock" in cyc[0].message
    assert "Beta._lock" in cyc[0].message


def test_g4_no_inversion_for_consistent_order(tmp_path):
    # both nestings go Alpha -> Beta: a DAG, not a cycle
    consistent = G4_ABBA_B.replace(
        "                self._alpha.poke_back()", "                pass")
    res = lint_tree(tmp_path, {
        "weaviate_tpu/runtime/alpha.py": G4_ABBA_A,
        "weaviate_tpu/runtime/beta.py": consistent,
    })
    assert [v for v in res.violations if "inversion" in v.message] == []


def test_g4_caller_holds_helper_contributes_graph_edges(tmp_path):
    # the nested acquisition happens inside a "Caller holds" helper —
    # the graph must still see holder -> inner (kv.py's WAL append idiom)
    helper_a = """
        import threading

        class Alpha:
            def __init__(self, beta):
                self._lock = threading.Lock()
                self._beta = beta

            def ping(self):
                with self._lock:
                    self._tail()

            def _tail(self):
                \"\"\"Caller holds ``_lock``.\"\"\"
                self._beta.poke()

            def poke_back(self):
                with self._lock:
                    pass
    """
    res = lint_tree(tmp_path, {
        "weaviate_tpu/runtime/alpha.py": helper_a,
        "weaviate_tpu/runtime/beta.py": G4_ABBA_B,
    })
    cyc = [v for v in res.violations if "inversion" in v.message]
    assert len(cyc) == 1


def test_g4_tuple_unpack_write_outside_lock(tmp_path):
    src = """
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()

            def clear(self):
                self._head, self._tail = None, None   # two torn writes

            def swap(self):
                with self._lock:
                    t, self._head = self._head, None  # held: fine
                return t
    """
    res = lint_tree(tmp_path, {"weaviate_tpu/runtime/fx.py": src})
    g4 = [v for v in res.violations if v.check == "G4"]
    assert len(g4) == 2
    assert all(v.line == 9 for v in g4)


def test_g4_innocuous_under_phrase_is_not_an_exemption(tmp_path):
    """'under _normal operating conditions' is prose, not a lock claim —
    the unlocked write must still flag."""
    src = """
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()

            def f(self):
                \"\"\"Runs fine under _normal operating conditions.\"\"\"
                self._n = 1
    """
    res = lint_tree(tmp_path, {"weaviate_tpu/runtime/fx.py": src})
    assert [v.check for v in res.violations] == ["G4"]


def test_g4_multi_item_with_orders_left_to_right(tmp_path):
    """``with self._a, self._b:`` acquires a then b — an opposite
    nesting elsewhere is a real ABBA and must flag."""
    src = """
        import threading

        class S:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def fwd(self):
                with self._a, self._b:
                    pass

            def rev(self):
                with self._b:
                    with self._a:
                        pass
    """
    res = lint_tree(tmp_path, {"weaviate_tpu/runtime/fx.py": src})
    assert any("inversion" in v.message for v in res.violations)


def test_g4_docstring_lock_names_match_whole_tokens():
    """A 'Caller holds ``_flush_lock``' doc must not seed ``_lock`` as
    held (substring!) — phantom held-edges would fabricate inversions."""
    import ast as _ast

    from tools.graftlint.core import FileContext
    from tools.graftlint.core import _ClassLocks, held_from_docstring

    src = textwrap.dedent("""
        import threading

        class Bucket:
            def __init__(self):
                self._lock = threading.Lock()
                self._flush_lock = threading.Lock()
    """)
    cls = _ast.parse(src).body[1]
    cl = _ClassLocks(cls, "weaviate_tpu/storage/fx.py")
    held = held_from_docstring("Caller holds ``_flush_lock``.", cl)
    assert held == ["weaviate_tpu/storage/fx.py:Bucket._flush_lock"]
    # naming _lock itself still resolves to _lock only
    held2 = held_from_docstring("Caller holds ``_lock``.", cl)
    assert held2 == ["weaviate_tpu/storage/fx.py:Bucket._lock"]


def test_g3_partial_scratch_still_exceeds_budget(tmp_path):
    """Resolved entries alone over budget must flag even when another
    entry cannot be sized — total is a lower bound."""
    src = """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kern(q_ref, x_ref, out_ref):
            out_ref[:] = q_ref[:]

        def big(q, n, d):
            return pl.pallas_call(
                kern,
                grid=(1,),
                scratch_shapes=[pltpu.VMEM((2048, 2048), jnp.float32),
                                pltpu.VMEM((n, d), jnp.float32)],
            )(q, q)
    """
    res = lint_tree(tmp_path, {"weaviate_tpu/ops/fixture.py": src})
    assert any("VMEM" in v.message for v in res.violations
               if v.check == "G3")


def test_g3_requires_a_real_pallas_import(tmp_path):
    """A comment mentioning pallas must not subject host-side helpers
    (or their block_rows-style params) to kernel alignment rules."""
    src = """
        # we route scans through the pallas kernels when on TPU

        def plan(n, block_rows: int = 100, tile_n: int = 100):
            return n // block_rows
    """
    res = lint_tree(tmp_path, {"weaviate_tpu/engine/fixture.py": src})
    assert [v for v in res.violations if v.check == "G3"] == []


def test_g3_host_side_param_names_not_dragged_in(tmp_path):
    """Only the exact kernel tile params are alignment-checked — a
    host chunking knob named block_rows is not a tile."""
    src = """
        from jax.experimental import pallas as pl

        def plan(n, block_rows: int = 100):
            return n // block_rows
    """
    res = lint_tree(tmp_path, {"weaviate_tpu/engine/fixture.py": src})
    assert [v for v in res.violations if v.check == "G3"] == []


def test_cache_is_keyed_on_checker_set(tmp_path):
    """A run with a checkers subset must not poison the full run."""
    from tools.graftlint.g4_locks import LockDisciplineChecker

    root = write_tree(tmp_path, {"weaviate_tpu/engine/fx.py": BASE_SRC})
    res_sub = run(["weaviate_tpu"], root, use_cache=True,
                  checkers=[LockDisciplineChecker()])
    assert res_sub.violations == []  # G4 sees nothing here
    res_full = run(["weaviate_tpu"], root, use_cache=True)
    assert [v.check for v in res_full.violations] == ["G1"]


# -- G5 metrics-conventions ---------------------------------------------------


G5_POSITIVE = """
    from weaviate_tpu.runtime.metrics import registry

    ok = registry.counter("weaviate_tpu_good_total", "documented")
    bad_name = registry.gauge("camelCaseGauge", "help")          # P1
    bad_prefix = registry.counter("other_ns_total", "help")      # P2
    no_help = registry.counter("weaviate_tpu_nohelp_total", "")  # P3
    bad_label = registry.histogram(
        "weaviate_tpu_lat_seconds", "help", ("badLabel",))       # P4
"""

G5_NEGATIVE = """
    from weaviate_tpu.runtime.metrics import registry

    a = registry.counter("weaviate_tpu_reqs_total", "requests served",
                         ("collection", "shard"))
    b = registry.histogram("weaviate_tpu_lat_seconds", "latency", ("op",))

    def dynamic(name):
        return registry.counter(name, "runtime lint covers dynamics")
"""


def test_g5_flags_bad_registrations(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/runtime/fx.py": G5_POSITIVE})
    g5 = [v for v in res.violations if v.check == "G5"]
    msgs = " | ".join(v.message for v in g5)
    # camelCaseGauge violates naming AND prefix -> 5 findings for 4 sites
    assert len(g5) == 5
    assert "camelCaseGauge" in msgs and "not snake_case" in msgs
    assert "weaviate_tpu_" in msgs        # prefix rule
    assert "HELP" in msgs
    assert "badLabel" in msgs


def test_g5_accepts_clean_and_skips_dynamic(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/runtime/fx.py": G5_NEGATIVE})
    assert [v for v in res.violations if v.check == "G5"] == []


G5_TIMING_POSITIVE = """
    from weaviate_tpu.runtime.metrics import registry

    # P1: timing metric, no unit suffix, no unit in HELP
    lat = registry.histogram("weaviate_tpu_scan_duration",
                             "how long scans take")

    def record(sp):
        entry = {
            "wall_s": 1.2,          # P2: ambiguous unit suffix
            "device_seconds": 0.5,  # P3: nonstandard timing unit
            "qps": 1000.0,          # fine
        }
        entry["host_time"] = 0.7    # P4: unit stated nowhere
        sp.set(dev_ms=0.5)          # P5: device_ms alias forks schema
        return entry
"""

G5_TIMING_NEGATIVE = """
    from weaviate_tpu.runtime.metrics import registry

    # unit in the name suffix
    a = registry.histogram("weaviate_tpu_scan_duration_seconds", "scans")
    # unit stated in HELP instead of the name
    b = registry.gauge("weaviate_tpu_scan_latency",
                       "p50 scan latency in milliseconds")

    def record(sp, rows):
        entry = {
            "wall_ms": 1200.0,      # repo convention: _ms
            "device_ms": 500.0,     # THE device-attributed field
            "device_batch_ms": 0.5, # historical bench key, unit stated
            "attempt_wall_ms": [1.0],
            "rtt_ms": 3.0,
        }
        entry["host_ms"] = 700.0
        sp.set(device_ms=0.5, wall_ms=1.2, dispatch_ms=0.1)
        return entry
"""


def test_g5_timing_conventions_flag_ambiguous_units(tmp_path):
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/runtime/fx.py": G5_TIMING_POSITIVE})
    g5 = [v for v in res.violations if v.check == "G5"]
    msgs = " | ".join(v.message for v in g5)
    assert len(g5) == 5, msgs
    assert "weaviate_tpu_scan_duration" in msgs      # P1 registration
    assert "'wall_s'" in msgs and "'wall_ms'" in msgs  # P2 + suggestion
    assert "'device_seconds'" in msgs                # P3
    assert "'host_time'" in msgs                     # P4 subscript assign
    assert "'dev_ms'" in msgs and "device_ms" in msgs  # P5 alias


def test_g5_timing_conventions_accept_repo_idiom(tmp_path):
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/runtime/fx.py": G5_TIMING_NEGATIVE})
    assert [v for v in res.violations if v.check == "G5"] == []


G5_HISTOGRAM_POSITIVE = """
    from weaviate_tpu.runtime.metrics import registry

    # P1: timing histogram not named *_seconds (le bounds are seconds)
    a = registry.histogram("weaviate_tpu_scan_duration_ms",
                           "scan latency in milliseconds")
    # P2: buckets declared out of order
    b = registry.histogram("weaviate_tpu_drain_seconds", "drain time",
                           (), buckets=(0.1, 0.05, 1.0))
    # P3: duplicated bound
    c = registry.histogram("weaviate_tpu_wait_latency_seconds", "waits",
                           ("op",), buckets=(0.1, 0.1, 1.0))
"""

G5_HISTOGRAM_NEGATIVE = """
    from weaviate_tpu.runtime.metrics import registry

    # timing histogram with the *_seconds suffix + ascending buckets
    a = registry.histogram("weaviate_tpu_scan_duration_seconds", "scans",
                           ("op",), buckets=(0.01, 0.1, 1.0))
    # count histogram: not timey, integer buckets fine
    b = registry.histogram("weaviate_tpu_batch_size", "batch sizes", (),
                           buckets=(1, 2, 4, 8))
    # dynamic buckets: the runtime lint's job, not the static pass
    B = tuple(sorted([0.5, 0.1]))
    c = registry.histogram("weaviate_tpu_x_seconds", "x", (), buckets=B)
"""


def test_g5_histogram_conventions_flag_violations(tmp_path):
    """ISSUE 15 G5 growth: timing histograms must be *_seconds (their
    le bounds are seconds repo-wide) and literal bucket sets must be
    strictly ascending."""
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/runtime/fx.py": G5_HISTOGRAM_POSITIVE})
    g5 = [v for v in res.violations if v.check == "G5"]
    msgs = " | ".join(v.message for v in g5)
    assert len(g5) == 3, msgs
    assert "weaviate_tpu_scan_duration_ms" in msgs and "_seconds" in msgs
    assert "weaviate_tpu_drain_seconds" in msgs and "ascending" in msgs
    assert "weaviate_tpu_wait_latency_seconds" in msgs


def test_g5_histogram_conventions_accept_clean(tmp_path):
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/runtime/fx.py": G5_HISTOGRAM_NEGATIVE})
    assert [v for v in res.violations if v.check == "G5"] == []


G5_METER_POSITIVE = """
    from weaviate_tpu.runtime.metrics import registry

    # P1: time-accumulating counter in milliseconds
    a = registry.counter("weaviate_tpu_device_ms_total", "device ms")
    # P2: seconds meter missing the _total suffix
    b = registry.counter("weaviate_tpu_tenant_seconds", "tenant time",
                         ("collection", "tenant"))
"""

G5_METER_NEGATIVE = """
    from weaviate_tpu.runtime.metrics import registry

    # THE metering shape: seconds + _total
    a = registry.counter("weaviate_tpu_device_seconds_total", "chip time",
                         ("collection", "tenant"))
    # count counters are not meters — no unit token, no rule
    b = registry.counter("weaviate_tpu_requests_total", "requests")
    # *_seconds HISTOGRAMS stay governed by the histogram rule alone
    c = registry.histogram("weaviate_tpu_drain_seconds", "drain", ("op",))
"""


def test_g5_meter_counters_must_be_seconds_total(tmp_path):
    """ISSUE 17 G5 growth: a time-accumulating counter is a meter, and
    meters are '*_seconds_total' — seconds repo-wide, _total per the
    Prometheus counter convention."""
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/runtime/fx.py": G5_METER_POSITIVE})
    g5 = [v for v in res.violations if v.check == "G5"]
    msgs = " | ".join(v.message for v in g5)
    assert len(g5) == 2, msgs
    assert "weaviate_tpu_device_ms_total" in msgs
    assert "weaviate_tpu_tenant_seconds" in msgs
    assert "_seconds_total" in msgs


def test_g5_meter_counters_accept_repo_shape(tmp_path):
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/runtime/fx.py": G5_METER_NEGATIVE})
    assert [v for v in res.violations if v.check == "G5"] == []


G5_EXPLAIN_POSITIVE = """
    import jax.numpy as jnp
    from weaviate_tpu.runtime import kernelscope

    def search(queries, allow_mask, k):
        d = jnp.sum(allow_mask)
        # P1: device value as an explain field — deferred host sync
        kernelscope.explain_note("store", selectivity=d)
        # P2: device expression built inline
        kernelscope.explain_note("store", rows=jnp.count_nonzero(allow_mask))
        return k
"""

G5_EXPLAIN_NEGATIVE = """
    import jax.numpy as jnp
    from weaviate_tpu.runtime import kernelscope

    def search(queries, allow_list, capacity, k):
        # host scalars only: lens, ints, precomputed fractions
        kernelscope.explain_note(
            "store", rows=capacity, queries=len(queries), k=k,
            filtered=allow_list is not None,
            selectivity=round(len(allow_list or ()) / capacity, 6))
        d = jnp.zeros((4,))
        return d
"""


def test_g5_explain_emissions_reject_device_args(tmp_path):
    """ISSUE 17 G5 growth: explain_note() args are eagerly evaluated
    and JSON-serialized at the API edge — a device arg is a deferred
    host sync the G1 hot-path pass cannot see. Piggybacks G1's taint
    machinery."""
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/engine/fx.py": G5_EXPLAIN_POSITIVE})
    g5 = [v for v in res.violations if v.check == "G5"]
    msgs = " | ".join(v.message for v in g5)
    assert len(g5) == 2, msgs
    assert "device value" in msgs and "host scalars" in msgs


def test_g5_explain_emissions_accept_host_scalars(tmp_path):
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/engine/fx.py": G5_EXPLAIN_NEGATIVE})
    assert [v for v in res.violations if v.check == "G5"] == []


def test_g5_explain_emissions_scoped_to_dispatch_path(tmp_path):
    """The taint rule only governs the dispatch-path modules — an API
    module may legitimately note a value numpy already materialized."""
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/api/fx.py": G5_EXPLAIN_POSITIVE})
    assert [v for v in res.violations if v.check == "G5"] == []


def test_g5_runtime_lint_checks_exemplar_grammar():
    """The runtime half validates OpenMetrics exemplar rendering: a
    well-formed registry passes; buckets ascending is enforced too."""
    from weaviate_tpu.runtime.metrics import MetricsRegistry

    from tools.graftlint import g5_metrics

    reg = MetricsRegistry()
    h = reg.histogram("weaviate_tpu_ok_seconds", "fine", ("op",),
                      buckets=(0.1, 1.0))
    h.labels("q").observe(0.05, exemplar={"trace_id": 'tr"icky\nid'})
    assert g5_metrics.lint(reg) == []
    reg2 = MetricsRegistry()
    reg2.histogram("weaviate_tpu_bad_seconds", "misordered", (),
                   buckets=(1.0, 0.1))
    assert any("ascending" in p for p in g5_metrics.lint(reg2))


def test_g5_timing_fields_scope_is_the_package(tmp_path):
    """The timing-field convention gates the package (``runtime/bands``
    reads ``device_ms`` by name); tests, tools and top-level scripts
    stay excluded."""
    src = """
        def section():
            return {"device_seconds": 0.5}
    """
    res = lint_tree(tmp_path, {
        "weaviate_tpu/runtime/fx.py": src,
        "tests/test_fx.py": src,
        "tools/fx/core.py": src,
        "fx.py": src,
    })
    g5 = {v.path for v in res.violations if v.check == "G5"}
    assert g5 == {"weaviate_tpu/runtime/fx.py"}


def test_g5_runtime_lint_reexported_through_shim():
    """tools/lint_metrics.py stays a working standalone module (the
    metrics-exposition tests load it by file path)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lint_metrics_shim", os.path.join(REPO_ROOT, "tools",
                                          "lint_metrics.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.lint) and callable(mod.main)
    from tools.graftlint.g5_metrics import lint as g5_lint
    assert mod.lint is g5_lint


# -- suppression mechanics ----------------------------------------------------


def test_inline_suppression_exact_line(tmp_path):
    src = """
        import jax

        def f(d):
            jax.block_until_ready(d)  # graftlint: disable=G1 — boundary
            jax.block_until_ready(d)
    """
    res = lint_tree(tmp_path, {"weaviate_tpu/engine/fx.py": src})
    g1 = [v for v in res.violations if v.check == "G1"]
    assert len(g1) == 1 and g1[0].line == 6  # only the unsuppressed one


def test_file_level_suppression(tmp_path):
    src = """
        # graftlint: disable-file=G1
        import jax

        def f(d):
            jax.block_until_ready(d)
    """
    res = lint_tree(tmp_path, {"weaviate_tpu/engine/fx.py": src})
    assert res.violations == []


def test_suppression_is_per_check_id(tmp_path):
    src = """
        import jax
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()

            def f(self, d):
                self._d = jax.block_until_ready(d)  # graftlint: disable=G4
    """
    res = lint_tree(tmp_path, {"weaviate_tpu/engine/fx.py": src})
    # G4 suppressed on that line; the G1 violation must survive
    assert [v.check for v in res.violations] == ["G1"]


# -- baseline mechanics -------------------------------------------------------


BASE_SRC = """
    import jax

    def f(d):
        jax.block_until_ready(d)
"""


def _baseline_for(res):
    return [{**v.to_dict(), "reason": "grandfathered for the test"}
            for v in res.violations]


def test_baseline_grandfathers_by_fingerprint(tmp_path):
    root = write_tree(tmp_path, {"weaviate_tpu/engine/fx.py": BASE_SRC})
    res = run(["weaviate_tpu"], root, use_cache=False)
    assert len(res.violations) == 1
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(_baseline_for(res)))
    res2 = run(["weaviate_tpu"], root, use_cache=False,
               baseline_path=str(bl))
    assert res2.violations == [] and len(res2.baselined) == 1
    assert res2.stale == [] and res2.clean


def test_baseline_survives_pure_line_motion(tmp_path):
    root = write_tree(tmp_path, {"weaviate_tpu/engine/fx.py": BASE_SRC})
    res = run(["weaviate_tpu"], root, use_cache=False)
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(_baseline_for(res)))
    # shift the violation down: same fingerprint, different line
    (tmp_path / "weaviate_tpu/engine/fx.py").write_text(
        "# a new leading comment\n# another\n"
        + textwrap.dedent(BASE_SRC))
    res2 = run(["weaviate_tpu"], root, use_cache=False,
               baseline_path=str(bl))
    assert res2.violations == [] and res2.stale == []


def test_stale_baseline_entry_fails_the_gate(tmp_path):
    root = write_tree(tmp_path, {"weaviate_tpu/engine/fx.py": BASE_SRC})
    res = run(["weaviate_tpu"], root, use_cache=False)
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(_baseline_for(res)))
    # fix the violation: the baseline entry is now stale -> gate fails
    (tmp_path / "weaviate_tpu/engine/fx.py").write_text(
        "def f(d):\n    return d\n")
    res2 = run(["weaviate_tpu"], root, use_cache=False,
               baseline_path=str(bl))
    assert res2.violations == []
    assert len(res2.stale) == 1
    assert not res2.clean


def test_update_baseline_prunes_stale_entries(tmp_path):
    root = write_tree(tmp_path, {"weaviate_tpu/engine/fx.py": BASE_SRC})
    res = run(["weaviate_tpu"], root, use_cache=False)
    entries = _baseline_for(res)
    entries.append({"check": "G1", "path": "weaviate_tpu/engine/gone.py",
                    "scope": "f", "message": "[host-sync] whatever",
                    "reason": "file was deleted"})
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(entries))
    res2 = run(["weaviate_tpu"], root, use_cache=False,
               baseline_path=str(bl))
    assert len(res2.stale) == 1
    pruned = core.update_baseline(res2.baselined + res2.violations,
                                  str(bl))
    assert pruned == 1
    kept = json.loads(bl.read_text())
    assert len(kept) == 1 and kept[0]["path"].endswith("fx.py")
    res3 = run(["weaviate_tpu"], root, use_cache=False,
               baseline_path=str(bl))
    assert res3.stale == [] and res3.violations == []


DOUBLE_SRC = """
    import jax

    def f(d):
        jax.block_until_ready(d)
        jax.block_until_ready(d)
"""


def test_baseline_count_gates_extra_identical_violations(tmp_path):
    """One entry grandfathers ONE occurrence: a second identical sync in
    the same scope must surface as NEW, not ride the existing entry."""
    root = write_tree(tmp_path, {"weaviate_tpu/engine/fx.py": BASE_SRC})
    res = run(["weaviate_tpu"], root, use_cache=False)
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(_baseline_for(res)))
    # duplicate the violation: same fingerprint, two occurrences
    (tmp_path / "weaviate_tpu/engine/fx.py").write_text(
        textwrap.dedent(DOUBLE_SRC))
    res2 = run(["weaviate_tpu"], root, use_cache=False,
               baseline_path=str(bl))
    assert len(res2.baselined) == 1 and len(res2.violations) == 1
    assert not res2.clean
    # count: 2 covers both; fixing one makes the entry stale again
    entries = json.loads(bl.read_text())
    entries[0]["count"] = 2
    bl.write_text(json.dumps(entries))
    res3 = run(["weaviate_tpu"], root, use_cache=False,
               baseline_path=str(bl))
    assert res3.clean and len(res3.baselined) == 2
    (tmp_path / "weaviate_tpu/engine/fx.py").write_text(
        textwrap.dedent(BASE_SRC))
    res4 = run(["weaviate_tpu"], root, use_cache=False,
               baseline_path=str(bl))
    assert len(res4.stale) == 1 and not res4.clean
    # --update-baseline shrinks the count instead of dropping the entry
    dropped = core.update_baseline(res4.baselined + res4.violations,
                                   str(bl))
    assert dropped == 0
    kept = json.loads(bl.read_text())
    assert len(kept) == 1 and "count" not in kept[0]
    res5 = run(["weaviate_tpu"], root, use_cache=False,
               baseline_path=str(bl))
    assert res5.clean


def test_baseline_entries_require_reasons(tmp_path):
    root = write_tree(tmp_path, {"weaviate_tpu/engine/fx.py": BASE_SRC})
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([{
        "check": "G1", "path": "weaviate_tpu/engine/fx.py",
        "scope": "f", "message": "[host-sync] x"}]))  # no reason
    res = run(["weaviate_tpu"], root, use_cache=False,
              baseline_path=str(bl))
    assert any("reason" in e for e in res.errors)
    assert not res.clean


# -- caching ------------------------------------------------------------------


def test_cache_reuses_and_invalidates_on_change(tmp_path):
    root = write_tree(tmp_path, {"weaviate_tpu/engine/fx.py": BASE_SRC})
    res1 = run(["weaviate_tpu"], root, use_cache=True)
    assert len(res1.violations) == 1
    assert os.path.exists(os.path.join(root, ".graftlint_cache.json"))
    # cached second run: same result
    res2 = run(["weaviate_tpu"], root, use_cache=True)
    assert checks(res2) == checks(res1)
    # edit the file: cache must invalidate, violation disappears
    (tmp_path / "weaviate_tpu/engine/fx.py").write_text(
        "def f(d):\n    return d\n")
    res3 = run(["weaviate_tpu"], root, use_cache=True)
    assert res3.violations == []


# -- G6 timeout-discipline -----------------------------------------------------


G6_POSITIVE = """
    import http.client
    import urllib.request
    from weaviate_tpu.cluster.transport import rpc

    def call_peer(addr):
        return rpc(addr, "/op", {"x": 1})                 # P1: no timeout

    def raw_conn(host, port):
        c = http.client.HTTPConnection(host, port)        # P2: no timeout
        return c

    def fetch(url):
        with urllib.request.urlopen(url) as r:            # P3: no timeout
            return r.read()
"""

G6_ALIASED_POSITIVE = """
    import weaviate_tpu.cluster.transport as t

    def call_peer(addr):
        return t.rpc(addr, "/op", {})                     # aliased module
"""

G6_NEGATIVE = """
    import http.client
    import urllib.request
    from weaviate_tpu.cluster.transport import rpc

    def call_peer(addr, budget):
        a = rpc(addr, "/op", {}, timeout=2.0)             # explicit
        b = rpc(addr, "/op", {}, timeout=None)            # deliberate opt-in
        return a, b

    def raw_conn(host, port):
        return http.client.HTTPConnection(host, port, timeout=5.0)

    def fetch(url):
        with urllib.request.urlopen(url, None, 10.0) as r:  # positional
            return r.read()

    def not_transport(client):
        return client.rpc("/op")                          # unrelated .rpc
"""


def test_g6_flags_unbounded_boundaries(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/cluster/fx.py": G6_POSITIVE})
    g6 = [v for v in res.violations if v.check == "G6"]
    msgs = " | ".join(v.message for v in g6)
    assert len(g6) == 3, msgs
    assert "transport.rpc call without an explicit timeout" in msgs
    assert "HTTPConnection constructed without timeout" in msgs
    assert "urlopen without a timeout" in msgs


def test_g6_resolves_module_alias(tmp_path):
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/cluster/fx.py": G6_ALIASED_POSITIVE})
    assert [v.check for v in res.violations] == ["G6"]


def test_g6_accepts_explicit_and_deliberate_none(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/cluster/fx.py": G6_NEGATIVE})
    assert [v for v in res.violations if v.check == "G6"] == []


# -- G7 durability-discipline ---------------------------------------------------


G7_POSITIVE = """
    import os

    def swap_state(tmp, final):
        os.replace(tmp, final)                   # P1: bare rename

    def rewrite(path, blob):
        with open(path + ".tmp", "wb") as f:     # P2: wb, fn never fsyncs
            f.write(blob)
        os.replace(path + ".tmp", path)          # P3: bare rename again
"""

G7_NEGATIVE = """
    import os

    from weaviate_tpu.storage import fsutil

    def swap_state(tmp, final):
        fsutil.atomic_replace(tmp, final)        # the sanctioned path

    def rewrite(path, blob):
        with open(path + ".tmp", "wb") as f:     # wb + fsync: fine
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        fsutil.atomic_replace(path + ".tmp", path)

    def reset_log(path):
        f = open(path, "wb")                     # truncate-reset pattern
        f.flush()
        os.fsync(f.fileno())
        return f

    def quarantine(path):
        os.replace(path, path + ".corrupt")      # evidence move: exempt
"""


def test_g7_flags_bare_replace_and_unsynced_wb(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/storage/fx.py": G7_POSITIVE})
    g7 = [v for v in res.violations if v.check == "G7"]
    msgs = " | ".join(v.message for v in g7)
    assert len(g7) == 3, msgs
    assert "bare os.replace" in msgs
    assert 'open(..., "wb") in a function that never fsyncs' in msgs


def test_g7_accepts_fsutil_fsync_and_quarantine(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/storage/fx.py": G7_NEGATIVE})
    assert [v for v in res.violations if v.check == "G7"] == []


def test_g7_guarded_write_is_not_an_fsync(tmp_path):
    """fsutil.guarded_write writes (and tears) but never fsyncs — a
    'wb' writer that only guards must still be flagged."""
    res = lint_tree(tmp_path, {"weaviate_tpu/storage/fx.py": """
        from weaviate_tpu.storage import fsutil

        def write_guarded_only(path, blob):
            with open(path, "wb") as f:
                fsutil.guarded_write(f, blob, "segment.write.mid")
    """})
    g7 = [v for v in res.violations if v.check == "G7"]
    assert len(g7) == 1 and "never fsyncs" in g7[0].message


def test_g7_scope_covers_state_owners_only(tmp_path):
    """storage/cluster/engine + crashtest own durable state;
    api/runtime/tests do not (their writes are reports/sockets)."""
    res = lint_tree(tmp_path, {
        "weaviate_tpu/storage/fx.py": G7_POSITIVE,
        "weaviate_tpu/cluster/fx.py": G7_POSITIVE,
        "weaviate_tpu/engine/fx.py": G7_POSITIVE,
        "tools/crashtest/fx.py": G7_POSITIVE,
        "weaviate_tpu/api/fx.py": G7_POSITIVE,
        "weaviate_tpu/runtime/fx.py": G7_POSITIVE,
        "tests/test_fx.py": G7_POSITIVE,
    })
    flagged = {v.path for v in res.violations if v.check == "G7"}
    assert flagged == {"weaviate_tpu/storage/fx.py",
                       "weaviate_tpu/cluster/fx.py",
                       "weaviate_tpu/engine/fx.py",
                       "tools/crashtest/fx.py"}


def test_g7_fsutil_itself_is_exempt(tmp_path):
    """fsutil IS the audited implementation — its own os.replace is the
    one the rest of the tree is routed through."""
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/storage/fsutil.py": G7_POSITIVE})
    assert [v for v in res.violations if v.check == "G7"] == []


def test_g7_baseline_stays_empty_for_storage_engine_cluster():
    """ISSUE 9 acceptance: the durable tree itself carries ZERO G7
    grandfathers — the fsync ordering was fixed by routing through
    fsutil, not baselined."""
    entries = core.load_baseline(core.default_baseline_path(REPO_ROOT))
    g7_state = [e for e in entries
                if e.get("check") == "G7"
                and str(e.get("path", "")).startswith("weaviate_tpu/")]
    assert g7_state == [], (
        "G7 baseline entries for weaviate_tpu/ are not allowed — route "
        "the write through storage/fsutil instead:\n"
        + "\n".join(str(e) for e in g7_state))


def test_g6_scope_is_production_tree_only(tmp_path):
    """Serving-path discipline: tests/tools stay out of G6 scope (they
    stub transports and probe dead ports on purpose)."""
    res = lint_tree(tmp_path, {
        "weaviate_tpu/cluster/fx.py": G6_POSITIVE,
        "tests/test_fx.py": G6_POSITIVE,
        "tools/fx.py": G6_POSITIVE,
    })
    assert {v.path for v in res.violations if v.check == "G6"} == \
        {"weaviate_tpu/cluster/fx.py"}


def test_g6_repo_baseline_names_only_reasoned_bootstrap_site():
    """The ONE grandfathered G6 site is the gossip bootstrap join —
    every serving-path transport call carries an explicit timeout."""
    entries = [e for e in core.load_baseline(
        core.default_baseline_path(REPO_ROOT)) if e["check"] == "G6"]
    assert [e["path"] for e in entries] == \
        ["weaviate_tpu/cluster/membership.py"]
    assert "bootstrap" in entries[0]["reason"]


# -- G8 partition-discipline --------------------------------------------------


G8_POSITIVE = """
    import jax.sharding
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P   # P1: import outside home

    def place(mesh, arr):
        spec = P(None, "shard")                   # P2: literal spec
        other = jax.sharding.PartitionSpec("shard")  # P3: dotted literal
        return NamedSharding(mesh, spec), other
"""

G8_NEGATIVE = """
    from jax.sharding import Mesh, NamedSharding

    from weaviate_tpu.parallel import partition

    def place(mesh, arr, allow):
        specs = partition.match_partition_rules(
            partition.SEARCH_RULES, {"x": arr, "allow_rows": allow}, mesh)
        return NamedSharding(mesh, specs["x"]), partition.row_sharding(
            mesh, dim=1)
"""


def test_g8_flags_spec_import_and_literals(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/parallel/fx.py": G8_POSITIVE})
    g8 = [v for v in res.violations if v.check == "G8"]
    msgs = " | ".join(v.message for v in g8)
    assert len(g8) == 3, msgs
    assert "imported outside" in msgs
    assert "hand-written P(...)" in msgs or "literal" in msgs


def test_g8_accepts_rule_table_resolution(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/parallel/fx.py": G8_NEGATIVE})
    assert [v for v in res.violations if v.check == "G8"] == []


def test_g8_partition_home_is_exempt(tmp_path):
    """partition.py IS the rule table — the one audited home for
    PartitionSpec construction."""
    res = lint_tree(
        tmp_path, {"weaviate_tpu/parallel/partition.py": G8_POSITIVE})
    assert [v for v in res.violations if v.check == "G8"] == []


def test_g8_scope_is_production_tree_only(tmp_path):
    """Tests and benches build specs for fixtures; product code must
    not."""
    res = lint_tree(tmp_path, {
        "weaviate_tpu/engine/fx.py": G8_POSITIVE,
        "tests/test_fx.py": G8_POSITIVE,
        "tools/fx.py": G8_POSITIVE,
    })
    assert {v.path for v in res.violations if v.check == "G8"} == \
        {"weaviate_tpu/engine/fx.py"}


def test_g8_baseline_stays_empty_for_weaviate_tpu():
    """ISSUE 13 acceptance: zero hand-wired PartitionSpec literals
    remain outside parallel/partition.py — placement was CENTRALIZED
    into the rule tables, not grandfathered."""
    entries = [e for e in core.load_baseline(
        core.default_baseline_path(REPO_ROOT)) if e.get("check") == "G8"]
    assert entries == [], (
        "G8 baseline entries are not allowed — resolve the spec "
        "through partition.match_partition_rules instead:\n"
        + "\n".join(str(e) for e in entries))


# -- CLI ----------------------------------------------------------------------


def test_cli_json_output_and_exit_codes(tmp_path):
    root = write_tree(tmp_path, {"weaviate_tpu/engine/fx.py": BASE_SRC})
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--json", "--no-cache",
         "--root", root, "weaviate_tpu"],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT)
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)
    assert payload["violations"] and \
        payload["violations"][0]["check"] == "G1"


# -- the whole-repo tier-1 gate ----------------------------------------------


def test_repo_gate_zero_nonbaselined_violations():
    """Every future PR runs this: the production tree must be clean
    modulo the checked-in baseline, and the baseline must not be stale.
    The paths are ``python -m tools.graftlint``'s defaults."""
    res = run(["weaviate_tpu", "tools/crashtest"],
              REPO_ROOT, use_cache=False,
              baseline_path=core.default_baseline_path(REPO_ROOT))
    assert res.errors == []
    assert res.stale == [], (
        "stale baseline entries — the violation was fixed; run "
        "python -m tools.graftlint --update-baseline")
    assert res.violations == [], (
        "new graftlint violations:\n" + "\n".join(
            f"{v.path}:{v.line}: {v.check} {v.message}"
            for v in res.violations))
    assert res.files > 50  # sanity: the walk really saw the tree


def test_repo_baseline_entries_all_have_reasons():
    entries = core.load_baseline(core.default_baseline_path(REPO_ROOT))
    for e in entries:
        assert str(e.get("reason", "")).strip(), e


def test_g1_baseline_stays_empty_for_engine():
    """ISSUE 7 acceptance: the two engine/store.py G1 entries (search
    result transfer, live_count int()) were retired by REDESIGN — the
    transfer moved behind DeviceResultHandle/tracing.d2h at the API
    boundary and live_count became a host counter. A host sync creeping
    back into engine/ must be FIXED (async handle, or routed through the
    sanctioned boundary), never re-baselined."""
    entries = core.load_baseline(core.default_baseline_path(REPO_ROOT))
    g1_engine = [e for e in entries
                 if e.get("check") == "G1"
                 and str(e.get("path", "")).startswith(
                     "weaviate_tpu/engine/")]
    assert g1_engine == [], (
        "G1 host-sync baseline entries for engine/ are not allowed "
        "anymore — fix the sync instead of grandfathering it:\n"
        + "\n".join(str(e) for e in g1_engine))


# -- whole-program machinery: ProgramIndex + G9/G10/G11 (ISSUE 20) ------------


TRANSFER_STUB = """
    import threading

    class TransferPipeline:
        def submit(self, value, callback):
            callback(value, None, 0.0, 0.0)
"""

G9_DRAIN_SINK = """
    from weaviate_tpu.runtime.transfer import TransferPipeline
    from weaviate_tpu.engine.post import settle

    class Search:
        def __init__(self):
            self._pipe = TransferPipeline()

        def kick(self, batch):
            self._pipe.submit(batch, self._on_done)

        def _on_done(self, value, err, t0, t1):
            settle(value)
"""

G9_DRAIN_HELPER_POS = """
    import jax

    def settle(v):
        jax.block_until_ready(v)   # P: sync on the drain thread
"""

G9_DRAIN_HELPER_NEG = """
    def settle(v):
        return list(v)             # N: host-only post-processing
"""


def test_g9_drain_callback_sync_across_modules(tmp_path):
    """Rule 1 positive: the sync hides two hops from the submit — in a
    helper module reached from the callback through a typed receiver."""
    res = lint_tree(tmp_path, {
        "weaviate_tpu/runtime/transfer.py": TRANSFER_STUB,
        "weaviate_tpu/engine/sink.py": G9_DRAIN_SINK,
        "weaviate_tpu/engine/post.py": G9_DRAIN_HELPER_POS,
    }, paths=["weaviate_tpu"])
    g9 = [v for v in res.violations if v.check == "G9"]
    assert len(g9) == 1
    assert g9[0].path == "weaviate_tpu/engine/post.py"
    assert "block_until_ready" in g9[0].message
    assert "Search._on_done" in g9[0].message  # names the seed callback


def test_g9_drain_callback_host_only_is_clean(tmp_path):
    res = lint_tree(tmp_path, {
        "weaviate_tpu/runtime/transfer.py": TRANSFER_STUB,
        "weaviate_tpu/engine/sink.py": G9_DRAIN_SINK,
        "weaviate_tpu/engine/post.py": G9_DRAIN_HELPER_NEG,
    }, paths=["weaviate_tpu"])
    assert [v for v in res.violations if v.check == "G9"] == []


def test_g9_transfer_module_itself_is_exempt(tmp_path):
    """The drain in transfer.py performs THE sanctioned sync — rule 1
    must not flag the pipeline's own machinery."""
    res = lint_tree(tmp_path, {
        "weaviate_tpu/runtime/transfer.py": """
            import jax

            class TransferPipeline:
                def submit(self, value, callback):
                    self._cb = callback

                def _run(self, value):
                    jax.block_until_ready(value)  # the one blocking D2H
                    self._cb(value, None, 0.0, 0.0)
        """,
    }, paths=["weaviate_tpu"])
    assert [v for v in res.violations if v.check == "G9"] == []


G9_LOCK_IO_POS = """
    import os
    import threading
    from weaviate_tpu.storage import fsutil

    class Store:
        def __init__(self, path):
            self._lock = threading.Lock()
            self.path = path

        def put(self, b):
            with self._lock:
                self._persist(b)          # P1: reaches fsync under lock

        def checkpoint(self, fd):
            with self._lock:
                os.fsync(fd)              # P2: direct fsync under lock

        def _persist(self, b):
            fsutil.fsync_file(self.path)
"""


def test_g9_io_under_db_lock_direct_and_through_call(tmp_path):
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/db/store9.py": G9_LOCK_IO_POS},
                    paths=["weaviate_tpu"])
    g9 = sorted((v.line, v.message) for v in res.violations
                if v.check == "G9")
    assert len(g9) == 2
    assert "fsync" in g9[0][1] and "fsync" in g9[1][1]
    assert any("Store._persist" in m for _l, m in g9)  # witness chain


def test_g9_lock_io_scoped_to_db_engine_classes(tmp_path):
    """The same shape under a runtime/-class lock is not rule 2's
    business (G4 covers ordering; the reader-stall contract is the
    db/engine serving path's)."""
    res = lint_tree(tmp_path,
                    {"weaviate_tpu/runtime/store9.py": G9_LOCK_IO_POS},
                    paths=["weaviate_tpu"])
    assert [v for v in res.violations if v.check == "G9"] == []


def test_g9_io_outside_critical_section_is_clean(tmp_path):
    res = lint_tree(tmp_path, {"weaviate_tpu/db/store9.py": """
        import threading
        from weaviate_tpu.storage import fsutil

        class Store:
            def __init__(self, path):
                self._lock = threading.Lock()
                self.path = path

            def put(self, b):
                with self._lock:
                    self._buf = b
                fsutil.fsync_file(self.path)   # after release: fine
    """}, paths=["weaviate_tpu"])
    assert [v for v in res.violations if v.check == "G9"] == []


G10_DEV_HELPER = """
    import jax.numpy as jnp

    def embed(x):
        return jnp.tanh(x)
"""

G10_CALLER_POS = """
    import numpy as np
    from weaviate_tpu.ops.dev10 import embed

    def pull(x):
        return np.asarray(embed(x))     # P: hidden cross-module sync
"""


def test_g10_flags_cross_module_device_taint(tmp_path):
    res = lint_tree(tmp_path, {
        "weaviate_tpu/ops/dev10.py": G10_DEV_HELPER,
        "weaviate_tpu/engine/use10.py": G10_CALLER_POS,
    }, paths=["weaviate_tpu"])
    g10 = [v for v in res.violations if v.check == "G10"]
    assert len(g10) == 1
    assert g10[0].path == "weaviate_tpu/engine/use10.py"
    assert "embed" in g10[0].message


def test_g10_flags_typed_receiver_method_return(tmp_path):
    res = lint_tree(tmp_path, {
        "weaviate_tpu/ops/dev10.py": """
            import jax.numpy as jnp

            class Scorer:
                def score(self, q):
                    return jnp.dot(q, q)
        """,
        "weaviate_tpu/engine/use10.py": """
            from weaviate_tpu.ops.dev10 import Scorer

            class Searcher:
                def __init__(self):
                    self._dev = Scorer()

                def worst(self, q):
                    return float(self._dev.score(q))   # P: hidden sync
        """,
    }, paths=["weaviate_tpu"])
    g10 = [v for v in res.violations if v.check == "G10"]
    assert len(g10) == 1
    assert "Scorer.score" in g10[0].message


def test_g10_host_returning_helper_is_clean(tmp_path):
    res = lint_tree(tmp_path, {
        "weaviate_tpu/ops/dev10.py": """
            import numpy as np
            import jax.numpy as jnp

            def embed(x):
                return np.asarray(jnp.tanh(x))   # helper pays the sync
        """,
        "weaviate_tpu/engine/use10.py": G10_CALLER_POS,
    }, paths=["weaviate_tpu"])
    assert [v for v in res.violations if v.check == "G10"] == []


def test_g10_sink_scope_matches_g1_hot_paths(tmp_path):
    """A sink outside the hot dirs (maintenance scripts, runtime glue)
    is not G10's business, even when the callee is device-returning."""
    res = lint_tree(tmp_path, {
        "weaviate_tpu/ops/dev10.py": G10_DEV_HELPER,
        "weaviate_tpu/cluster/use10.py": G10_CALLER_POS,
    }, paths=["weaviate_tpu"])
    assert [v for v in res.violations if v.check == "G10"] == []


def test_g10_known_device_funcs_left_to_g1(tmp_path):
    """Callees in G1's DEVICE_FUNCS registry are G1's per-file findings
    — G10 must not double-report the same sink."""
    res = lint_tree(tmp_path, {
        "weaviate_tpu/ops/dev10.py": """
            import jax.numpy as jnp

            def normalize(x):
                return jnp.abs(x)
        """,
        "weaviate_tpu/engine/use10.py": """
            import numpy as np
            from weaviate_tpu.ops.dev10 import normalize

            def pull(x):
                return np.asarray(normalize(x))
        """,
    }, paths=["weaviate_tpu"])
    assert [v for v in res.violations if v.check == "G10"] == []
    assert [v for v in res.violations if v.check == "G1"]  # G1 has it


def test_whole_program_cache_invalidation(tmp_path):
    """Editing ONLY the helper file must re-judge the (cached) caller:
    the ProgramIndex is rebuilt from cached facts every run, so an
    interprocedural verdict never goes stale behind the per-file cache."""
    files = {
        "weaviate_tpu/ops/dev10.py": """
            import numpy as np
            import jax.numpy as jnp

            def embed(x):
                return np.asarray(jnp.tanh(x))
        """,
        "weaviate_tpu/engine/use10.py": G10_CALLER_POS,
    }
    root = write_tree(tmp_path, files)
    res1 = run(["weaviate_tpu"], root, use_cache=True)
    assert [v for v in res1.violations if v.check == "G10"] == []
    # flip the helper to return a device value; caller file untouched
    (tmp_path / "weaviate_tpu/ops/dev10.py").write_text(
        textwrap.dedent(G10_DEV_HELPER))
    res2 = run(["weaviate_tpu"], root, use_cache=True)
    g10 = [v for v in res2.violations if v.check == "G10"]
    assert len(g10) == 1
    assert g10[0].path == "weaviate_tpu/engine/use10.py"


def test_g10_fix_stays_fixed_sabotage():
    """ISSUE 20 acceptance: pq_encode's np.asarray(_assign(...)) was a
    REAL pre-existing hidden sync found by G10 and fixed via
    tracing.d2h. Reverting the fix must re-trigger the checker."""
    src = open(os.path.join(REPO_ROOT, "weaviate_tpu/ops/pq.py")).read()
    fixed = ("(codes,) = tracing.d2h("
             "_assign(chunk, codebook.centroids, codebook.m))")
    assert fixed in src, "pq_encode no longer routes through tracing.d2h"
    sabotaged = src.replace(
        fixed + "\n        out[s : s + batch] = codes.astype(np.uint8)",
        "out[s : s + batch] = np.asarray(\n"
        "            _assign(chunk, codebook.centroids, codebook.m)\n"
        "        ).astype(np.uint8)")
    assert sabotaged != src
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, "weaviate_tpu/ops/pq.py")
        os.makedirs(os.path.dirname(p))
        with open(p, "w") as f:
            f.write(sabotaged)
        res = run(["weaviate_tpu"], td, use_cache=False)
    g10 = [v for v in res.violations if v.check == "G10"]
    assert len(g10) == 1 and "_assign" in g10[0].message


def _g11_checkers(inv_path):
    from tools.graftlint.g11_config import ConfigSurfaceChecker
    return [ConfigSurfaceChecker(inventory_path=str(inv_path))]


def _empty_inventory(tmp_path):
    p = tmp_path / "inv.json"
    p.write_text('{"reads": [], "dynamic": []}\n')
    return p


def test_g11_flags_unregistered_env_read(tmp_path):
    inv = _empty_inventory(tmp_path)
    res = lint_tree(tmp_path, {"weaviate_tpu/feature.py": """
        import os

        def on():
            return os.environ.get("WEAVIATE_TPU_FEATURE") == "1"
    """}, paths=["weaviate_tpu"], checkers=_g11_checkers(inv))
    g11 = [v for v in res.violations if v.check == "G11"]
    assert len(g11) == 1
    assert "WEAVIATE_TPU_FEATURE" in g11[0].message


def test_g11_flags_unregistered_dynamic_read(tmp_path):
    inv = _empty_inventory(tmp_path)
    res = lint_tree(tmp_path, {"weaviate_tpu/feature.py": """
        import os

        KNOB = "WEAVIATE_TPU_FEATURE"

        def on():
            return os.environ.get(KNOB) == "1"
    """}, paths=["weaviate_tpu"], checkers=_g11_checkers(inv))
    g11 = [v for v in res.violations if v.check == "G11"]
    assert len(g11) == 1
    assert "dynamic" in g11[0].message


def test_g11_registered_reads_and_reasoned_dynamic_pass(tmp_path):
    inv = tmp_path / "inv.json"
    inv.write_text(json.dumps({
        "reads": [{"name": "WEAVIATE_TPU_FEATURE",
                   "path": "weaviate_tpu/feature.py"}],
        "dynamic": [{"path": "weaviate_tpu/feature.py", "scope": "dyn",
                     "reason": "name composed from a prefix"}],
    }))
    res = lint_tree(tmp_path, {"weaviate_tpu/feature.py": """
        import os

        def on():
            return os.environ.get("WEAVIATE_TPU_FEATURE") == "1"

        def dyn(name):
            return os.environ.get("WEAVIATE_TPU_" + name)
    """}, paths=["weaviate_tpu"], checkers=_g11_checkers(inv))
    assert [v for v in res.violations if v.check == "G11"] == []


def test_g11_dynamic_entry_without_reason_rejected(tmp_path):
    inv = tmp_path / "inv.json"
    inv.write_text(json.dumps({
        "reads": [],
        "dynamic": [{"path": "weaviate_tpu/feature.py",
                     "scope": "dyn", "reason": "  "}],
    }))
    res = lint_tree(tmp_path, {"weaviate_tpu/feature.py": """
        import os

        def dyn(name):
            return os.environ.get("WEAVIATE_TPU_" + name)
    """}, paths=["weaviate_tpu"], checkers=_g11_checkers(inv))
    g11 = [v for v in res.violations if v.check == "G11"]
    assert len(g11) == 1 and "reason" in g11[0].message


def test_g11_stale_inventory_entry_flagged(tmp_path):
    inv = tmp_path / "inv.json"
    inv.write_text(json.dumps({
        "reads": [{"name": "WEAVIATE_TPU_GONE",
                   "path": "weaviate_tpu/feature.py"}],
        "dynamic": [],
    }))
    res = lint_tree(tmp_path, {"weaviate_tpu/feature.py": """
        def on():
            return True
    """}, paths=["weaviate_tpu"], checkers=_g11_checkers(inv))
    g11 = [v for v in res.violations if v.check == "G11"]
    assert len(g11) == 1 and "stale" in g11[0].message


def test_g11_accessor_promotion_registers_call_sites(tmp_path):
    """The repo idiom: _env_flag(name, default) reads os.environ with a
    param key. The accessor's own read is exempt; each literal call
    site is the registered read."""
    inv = tmp_path / "inv.json"
    inv.write_text(json.dumps({
        "reads": [{"name": "WEAVIATE_TPU_A",
                   "path": "weaviate_tpu/feature.py"},
                  {"name": "WEAVIATE_TPU_B",
                   "path": "weaviate_tpu/feature.py"}],
        "dynamic": [],
    }))
    res = lint_tree(tmp_path, {"weaviate_tpu/feature.py": """
        import os

        def _env_flag(name, default):
            raw = os.environ.get(name)
            return default if raw is None else raw == "1"

        def knobs():
            return _env_flag("WEAVIATE_TPU_A", False), \\
                _env_flag("WEAVIATE_TPU_B", True)
    """}, paths=["weaviate_tpu"], checkers=_g11_checkers(inv))
    assert [v for v in res.violations if v.check == "G11"] == []


def test_g11_config_py_is_exempt(tmp_path):
    inv = _empty_inventory(tmp_path)
    res = lint_tree(tmp_path, {"weaviate_tpu/config.py": """
        import os

        def anything():
            return os.environ.get("WEAVIATE_TPU_WHATEVER")
    """}, paths=["weaviate_tpu"], checkers=_g11_checkers(inv))
    assert [v for v in res.violations if v.check == "G11"] == []


def test_g11_env_inventory_cli(tmp_path):
    root = write_tree(tmp_path, {"weaviate_tpu/feature.py": """
        import os

        def on():
            return os.environ.get("WEAVIATE_TPU_FEATURE") == "1"
    """})
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--env-inventory",
         "--no-cache", "--root", root, "weaviate_tpu"],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stderr
    payload = json.loads(proc.stdout)
    assert {"name": "WEAVIATE_TPU_FEATURE",
            "path": "weaviate_tpu/feature.py"} in payload["reads"]


def test_changed_only_filters_by_path():
    from tools.graftlint.core import Result, Violation, filter_changed
    res = Result(
        violations=[Violation("G1", "weaviate_tpu/a.py", 1, 0, "m"),
                    Violation("G1", "weaviate_tpu/b.py", 1, 0, "m")],
        baselined=[Violation("G9", "weaviate_tpu/b.py", 2, 0, "m")],
        stale=[{"check": "G9", "path": "weaviate_tpu/a.py",
                "message": "m", "reason": "r"}],
        errors=["weaviate_tpu/b.py:1: syntax error: bad"],
        files=2)
    out = filter_changed(res, {"weaviate_tpu/a.py"})
    assert [v.path for v in out.violations] == ["weaviate_tpu/a.py"]
    assert out.baselined == []
    assert len(out.stale) == 1
    assert out.errors == []
    assert out.files == res.files


def test_repo_g9_baseline_entries_are_reasoned_clusters():
    """The 35 seed G9 findings are two known redesign-scale clusters:
    HNSW WAL-order-under-lock and kv backpressure-flush-under-shard-
    lock. Anything new must be FIXED, not added here."""
    entries = [e for e in core.load_baseline(
        core.default_baseline_path(REPO_ROOT)) if e["check"] == "G9"]
    assert entries, "G9 cluster baseline disappeared"
    for e in entries:
        assert e["path"].startswith(("weaviate_tpu/engine/hnsw",
                                     "weaviate_tpu/db/")), e
        assert "redesign-scale" in e["reason"], e


def test_repo_g10_baseline_stays_empty():
    """G10 findings get FIXED (route the transfer through tracing.d2h
    or a handle), never grandfathered."""
    entries = [e for e in core.load_baseline(
        core.default_baseline_path(REPO_ROOT)) if e["check"] == "G10"]
    assert entries == [], entries


def test_readme_documents_every_weaviate_tpu_knob():
    """ISSUE 20 acceptance: every WEAVIATE_TPU_* env read the live scan
    finds must be documented in README.md."""
    from tools.graftlint.g11_config import ConfigSurfaceChecker
    g11 = ConfigSurfaceChecker()
    run(["weaviate_tpu"], REPO_ROOT, use_cache=False, checkers=[g11])
    knobs = {e["name"] for e in g11.live_inventory()["reads"]
             if e["name"].startswith("WEAVIATE_TPU_")}
    assert knobs, "live scan found no WEAVIATE_TPU_* knobs"
    readme = open(os.path.join(REPO_ROOT, "README.md")).read()
    missing = sorted(k for k in knobs if k not in readme)
    assert missing == [], (
        "WEAVIATE_TPU_* knobs read by the code but undocumented in "
        f"README.md: {missing}")


def test_repo_env_inventory_matches_live_scan():
    """The checked-in inventory IS the config surface: regenerating it
    must be a no-op (otherwise someone added a read without running
    --update-env-inventory — G11 flags that too, but this pins the
    file itself, including counts)."""
    from tools.graftlint.g11_config import (ConfigSurfaceChecker,
                                            load_inventory)
    g11 = ConfigSurfaceChecker()
    run(["weaviate_tpu"], REPO_ROOT, use_cache=False, checkers=[g11])
    live = g11.live_inventory()
    inv = load_inventory(g11.inventory_path)
    assert live["reads"] == sorted(
        inv["reads"], key=lambda e: (e["name"], e["path"]))
