"""One run of one cell of BENCHMARK.json.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX. It starts the server in a child
(serve.py, the only process that holds the chip), creates the class over
REST, imports the seeded corpus over gRPC BatchObjects on one stream, reads a
seeded sample back by id, warms the batcher's shapes with the cell's own
traffic (steady, then in bursts), waits until the store has settled, drives
the traffic from worker processes (worker.py) for
``--seconds``, stops the server, judges every reply against the numpy
reference (reference.py) and prints the result line. ``setup_s`` runs from
process start to the window's first request; the reference's time is
outside both.

Everything that belongs to one cell is found by name from BENCHMARK.json:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``datagen/<generator>.py`` and, for a traced run, one reader per per-layer
metric, ``layer_metrics/<name>.json`` (a Prometheus delta) or ``<name>.py``.

``--rows N --rehearse`` walks the same code at a small size on whatever
backend JAX finds; such a run reports ``"rehearsal": true`` and never
``"correct": true``."""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import reference  # noqa: E402
import traffic  # noqa: E402
import wire  # noqa: E402

WARM_STEP_SECONDS = 1.0
WARM_LEVELS = 8
# requests of the bursts aimed at padded batch size b, in turn: b * x + y
BURST_LADDER = ((1, 1), (1, 2), (2, 0), (3, 0), (4, 0), (1, 0), (2.5, 0),
                (3.5, 0))
IDLE_CORES = 0.15
SETTLE_SECONDS = 150.0
SEGMENTS = "weaviate_tpu_lsm_segment_count"
TRACE_SECONDS = 2.0
BUCKETS = "weaviate_tpu_query_batcher_compile_bucket_total"
BACKGROUND = ("weaviate_tpu_lsm_compaction_duration_seconds",
              "weaviate_tpu_lsm_flush_duration_seconds")
COMPILE_SERIES = ("weaviate_tpu_compile_cache_events_total",
                  "weaviate_tpu_jit_compile_seconds_count")


def note(**obj) -> None:
    """An earlier line of the output: one JSON object."""
    print(json.dumps(obj), flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Child:
    """A subprocess that speaks one JSON object per line on its stdout."""

    def __init__(self, argv: list[str], env=None):
        self.proc = subprocess.Popen(
            [sys.executable] + argv, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True, bufsize=1, cwd=REPO, env=env)
        self.lock = threading.Lock()

    def send(self, line: str) -> None:
        self.proc.stdin.write(line + "\n")
        self.proc.stdin.flush()

    def read(self) -> dict:
        while True:
            line = self.proc.stdout.readline()
            if not line:
                raise RuntimeError(f"{self.proc.args[1]} ended early "
                                   f"(exit code {self.proc.wait()})")
            if line.startswith("{"):
                return json.loads(line)

    def ask(self, line: str) -> dict:
        with self.lock:
            self.send(line)
            return self.read()

    def end(self, timeout: float) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()
                self.proc.wait(timeout=timeout)
            except (subprocess.TimeoutExpired, OSError):
                self.proc.kill()
                self.proc.wait()


def find_cell(bench: dict, name: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(REPO, cfg_entry["file"])) as f:
        config = json.load(f)
    return cell, config, traffic.load(cell["traffic"])


def metrics_of(bench: dict, group: str, cell: str) -> list[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def run_phase(workers, name, clients, t0, t1, burst=None,
              out_dir=None) -> list[dict]:
    """One phase on every worker; -> their replies."""
    for w, worker in enumerate(workers):
        out = os.path.join(out_dir, f"w{w}.npz") if out_dir else None
        worker.send(json.dumps({"phase": name, "clients": clients, "t0": t0,
                                "t1": t1, "burst": burst, "out": out}))
    return [worker.read() for worker in workers]


def warm_up(workers, rest, clients: int) -> dict:
    """The cell's own traffic from 1, 2, 4, ... clients, a second each;
    then from all of them in levels of 2, 4, 8, 8, ... seconds, until a
    level of at least eight requests a client has passed in which nothing
    compiled and no padded batch size was dispatched for the first time (a
    slow cell reaches its large batches only after seconds of load).

    A padded batch size that steady load has still not dispatched would
    load its programs inside a window when it first occurs there, so bursts
    follow: n requests sent at the same instant, aimed at the smallest size
    b the clients can fill and the batcher has not dispatched. A slow
    server drains one or two requests, then all the rest: b + 1 or b + 2
    fill b. A fast one drains few at a time whatever arrives: 2b to 4b at
    once (each client sends several) may outrun it. Where a burst got
    something new dispatched or compiled, levels follow once more."""

    def phase(name, n, seconds, burst=None):
        t0 = time.time() + 0.05
        done = run_phase(workers, name, n, t0, t0 + seconds, burst)
        if any(d["failed"] for d in done):
            raise RuntimeError(f"warm-up request failed: {done}")
        return sum(d["sent"] for d in done)

    def state():
        page = rest.metrics()
        return (sum(page.total(s) for s in COMPILE_SERIES),
                sorted(page.by_label(BUCKETS, "b"), key=int))

    levels = []

    def until_settled():
        was, settled, step = state(), False, 0
        while len(levels) < WARM_LEVELS and not settled:
            seconds = min(8.0, 2.0 * 2 ** step)
            sent = phase(f"warm-all-{len(levels)}", clients, seconds)
            now = state()
            settled = (now == was and sent >= 8 * clients
                       and (seconds >= 8.0 or sent >= 32 * clients))
            levels.append({"seconds": seconds, "requests": sent,
                           "quiet": now == was})
            was, step = now, step + 1
        return was, settled

    n = 1
    while n < clients:
        phase(f"warm-{n}", n, WARM_STEP_SECONDS)
        n *= 2
    was, settled = until_settled()
    wanted = {1 << e for e in range((clients - 1).bit_length() + 1)}
    bursts = []
    while True:
        tried = [b for b, _ in bursts]
        missing = [b for b in wanted - {int(b) for b in state()[1]}
                   if tried.count(b) < len(BURST_LADDER)]
        if not missing:
            break
        b = min(missing)
        n = int(b * BURST_LADDER[tried.count(b)][0]
                + BURST_LADDER[tried.count(b)][1])
        each = -(-n // clients)
        phase(f"burst-{len(bursts)}", -(-n // each), 60.0, burst=each)
        bursts.append((b, n))
    if state() != was:
        was, settled = until_settled()
    return {"levels": levels, "settled": settled,
            "burst_sizes_by_size_aimed_at": {
                str(b): [n for was_b, n in bursts if was_b == b]
                for b in sorted({was_b for was_b, _ in bursts})},
            "buckets_dispatched": was[1], "buckets_never_filled": sorted(
                wanted - {int(b) for b in was[1]})}


def cpu_seconds(pid: int) -> float:
    """User + system CPU time a process has used so far."""
    with open(f"/proc/{pid}/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")


def canaries_sealed(rest, collection: str, rows: int) -> bool:
    """Whether driftwatch (on by default) has sealed its canary's ground
    truth on the whole corpus: ``/v1/debug/drift`` gives each canary's
    corpus token, which holds the row count. Sealing reads every object
    back from the store, beside the requests, for tens of seconds, and
    happens again at the next 30-s tick whenever rows were added since."""
    try:
        drift = json.loads(rest.request("GET", "/v1/debug/drift"))
    except (RuntimeError, ValueError):
        return True
    mine = [c for c in drift.get("canaries", {}).values()
            if c.get("collection") == collection]
    return not drift.get("enabled") or all(
        c.get("skipped") or str(rows) in (c.get("epoch_token") or "")
        for c in mine) and bool(mine)


def wait_until_settled(pid: int, rest, collection: str, rows: int) -> dict:
    """Import leaves flushes and compactions behind (how many segments a
    lookup walks depends on when they finish) and driftwatch reseals its
    canaries. Wait until the canaries are sealed on the whole corpus and
    the server has used under IDLE_CORES of a core for two seconds running
    (at most SETTLE_SECONDS): the window measures the settled store."""
    t_begin = time.time()
    quiet, sealed = 0, False
    while time.time() - t_begin < SETTLE_SECONDS:
        c0, t0 = cpu_seconds(pid), time.time()
        time.sleep(1.0)
        busy = (cpu_seconds(pid) - c0) / (time.time() - t0)
        quiet = quiet + 1 if busy < IDLE_CORES else 0
        if quiet >= 2:
            sealed = canaries_sealed(rest, collection, rows)
            if sealed:
                break
    return {"seconds": time.time() - t_begin, "idle": quiet >= 2,
            "canaries_sealed": sealed}


def percentile(sorted_values, q: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    rank = max(1, int(np.ceil(q / 100.0 * len(sorted_values))))
    return float(sorted_values[rank - 1])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=0,
                    help="rehearsal only: a smaller corpus")
    ap.add_argument("--rehearse", action="store_true",
                    help="walk the code off the chip; never reports correct")
    ap.add_argument("--fault", default="", help="tests only (serve.py)")
    ap.add_argument("--control", action="store_true",
                    help="create the class at the configuration's next "
                         "lower precision: has to come out not correct")
    args = ap.parse_args()
    if (args.rows or args.fault) and not args.rehearse:
        raise SystemExit("--rows and --fault are for --rehearse runs")

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, config, mix = find_cell(bench, args.workload)
    rows = args.rows or config["rows"]
    k = config["k"]
    tmp = tempfile.mkdtemp(prefix="wtpu-bench-")
    server = None
    workers: list[Child] = []
    try:
        server_args = [os.path.join("benchmarks", "serve.py"), "--data-dir",
                       os.path.join(tmp, "data")]
        if args.rehearse:
            server_args += ["--rehearse"]
        if args.fault:
            server_args += ["--fault", args.fault]
        env = {k_: v for k_, v in os.environ.items() if k_ != "BENCH_RUN"}
        server = Child(server_args, env=env)

        # the data, while the server starts
        t = time.time()
        datagen = load_module(os.path.join(
            HERE, "datagen", config["generator"] + ".py"), "bench_datagen")
        corpus, props, queries = datagen.generate(
            np.random.default_rng([args.seed, 1]), rows, config["dim"],
            config["generator_params"])
        pool_path = os.path.join(tmp, "pool.npy")
        np.save(pool_path, queries)
        datagen_s = time.time() - t

        ready = server.read()
        device = ready["device"]
        if device["count"] < cell["chips"]:
            raise SystemExit(f"cell needs {cell['chips']} chips, JAX has "
                             f"{device['count']}")
        server_start_s = time.time() - T_PROCESS_START
        rest = wire.Rest(ready["rest"])
        grpc = wire.Grpc(ready["grpc"])
        per_proc = -(-mix["clients"] // mix["processes"])
        for p in range(mix["processes"]):
            ids = list(range(p * per_proc,
                             min((p + 1) * per_proc, mix["clients"])))
            worker = Child([os.path.join("benchmarks", "worker.py")], env=env)
            worker.send(json.dumps({
                "port": ready["grpc"], "collection": config["collection"],
                "mix": mix, "k": k, "seed": args.seed,
                "clients": ids, "pool": pool_path}))
            workers.append(worker)

        klass = json.loads(json.dumps(config["class"]))
        if args.control:
            klass["vectorIndexConfig"].update(
                config["precision"]["control_class_override"])
        rest.create_class(klass)
        t = time.time()
        grpc.import_rows(config["collection"], corpus, props,
                         config["import_batch"])
        import_s = time.time() - t

        t = time.time()
        sample = np.random.default_rng([args.seed, 2]).choice(
            rows, size=min(config["readback_sample"], rows), replace=False)
        objects = []
        for i in sample:
            try:
                objects.append(rest.get_object(config["collection"],
                                               wire.obj_uuid(int(i))))
            except RuntimeError:
                objects.append(None)
        readback = reference.judge_readback(objects, sample, corpus, props)
        readback_s = time.time() - t

        t = time.time()
        for worker in workers:
            worker.read()   # ready
        warm = warm_up(workers, rest, mix["clients"])
        warm_s = time.time() - t
        settle = {"seconds": 0.0, "skipped": "control"} if args.control \
            else wait_until_settled(ready["pid"], rest,
                                    config["collection"], rows)

        # the window
        before = rest.metrics()
        cpu_before = cpu_seconds(ready["pid"])
        t0 = time.time() + 0.1
        t1 = t0 + args.seconds
        setup_s = t0 - T_PROCESS_START
        tracer = None
        trace_marks = {}
        if args.trace:
            def traced():
                time.sleep(max(0.0, t0 + 0.35 * args.seconds - time.time()))
                trace_marks["before"] = rest.metrics()
                server.ask("trace_start " + os.path.join(tmp, "trace"))
                time.sleep(min(TRACE_SECONDS, 0.3 * args.seconds))
                server.ask("trace_stop")
                trace_marks["after"] = rest.metrics()

            tracer = threading.Thread(target=traced)
            tracer.start()
        out_dir = os.path.join(tmp, "replies")
        os.makedirs(out_dir)
        done = run_phase(workers, "window", mix["clients"], t0, t1,
                         out_dir=out_dir)
        if tracer is not None:
            tracer.join()
        after = rest.metrics()
        server_cores = (cpu_seconds(ready["pid"]) - cpu_before) / args.seconds
        note(window={"seconds": args.seconds, "workers": done},
             compiles_in_window={
                 s: after.total(s) - before.total(s) for s in COMPILE_SERIES},
             dispatches_by_padded_batch={
                 b: n - before.by_label(BUCKETS, "b").get(b, 0.0)
                 for b, n in after.by_label(BUCKETS, "b").items()},
             background_in_window={
                 s + part: after.total(s + part) - before.total(s + part)
                 for s in BACKGROUND for part in ("_count", "_sum")},
             server_cpu_cores_in_window=server_cores,
             lsm_segments=after.total(SEGMENTS), settle=settle,
             warm_up=warm)

        store = server.ask("describe " + config["collection"])
        trace = server.ask("trace_reduce") if args.trace else None
        peak = server.ask("stats")["memory_peak_bytes"]
        server.send("stop")
        stopped = server.read()
        server.end(timeout=60)
        for worker in workers:
            worker.send(json.dumps({"exit": True}))
            worker.end(timeout=10)
        note(setup={"server_start_s": server_start_s, "datagen_s": datagen_s,
                    "import_s": import_s, "import_objects_per_s":
                    rows / import_s, "readback_s": readback_s,
                    "warm_up_s": warm_s, "settle_s": settle["seconds"],
                    "setup_s_without_settling": setup_s - settle["seconds"]},
             store=store, stopped=stopped)

        # the replies, and the reference outside every timed span
        t = time.time()
        parts = [np.load(os.path.join(out_dir, f"w{w}.npz"))
                 for w in range(len(workers))]
        replies = {key: np.concatenate([p[key] for p in parts])
                   for key in parts[0].files}
        verdict = reference.judge(replies, queries, corpus, props,
                                  config["metric"], k, mix.get("filter"),
                                  config["limits"])
        numbers = dict(verdict["numbers"], readback_mismatches=readback)
        reference_s = time.time() - t
        note(compared=numbers, reference_s=reference_s)

        shaped = (~replies["failed"]) & (replies["n_results"] == k)
        in_window = shaped & (replies["due"] + replies["latency"] <= t1)
        lat_ms = np.sort(replies["latency"][shaped]) * 1000.0
        attempted = int(len(shaped))
        failed = int(attempted - shaped.sum())
        if not len(lat_ms):
            raise RuntimeError("no reply in the window")
        end_to_end = {
            "qps": float(in_window.sum()) / args.seconds,
            "p50_ms": percentile(lat_ms, 50),
            "p95_ms": percentile(lat_ms, 95),
            "recall_at_k": verdict["recall_at_k"],
            "setup_s": setup_s,
        }
        done_at = (replies["due"] + replies["latency"])[in_window] - t0
        note(replies_per_second_of_window=np.bincount(
            done_at.astype(int), minlength=int(args.seconds)).tolist())
        note(latency={"samples": int(len(lat_ms)),
                      "beyond_p95": int(len(lat_ms) * 0.05),
                      "p99_ms": percentile(lat_ms, 99),
                      "mean_ms": float(lat_ms.mean()),
                      "max_ms": float(lat_ms[-1])},
             generator_late_ms={"mean": float(replies["late"].mean()) * 1e3,
                                "max": float(replies["late"].max()) * 1e3})
        correct = all(n["ok"] for n in numbers.values()) and failed == 0
        if "jax" in sys.modules:
            raise RuntimeError("the parent imported JAX")

        result = {"correct": bool(correct), "attempted": attempted,
                  "failed": failed, "metrics": {},
                  "device": dict(device, memory_peak_bytes=peak)}
        if args.trace:
            # the server's own series are read over the part of the window
            # before the profiler started: tracing stalls the host
            ctx = {"before": before, "after": trace_marks["before"],
                   "trace": trace,
                   "trace_marks": trace_marks, "store": store,
                   "device": device, "config": config, "mix": mix,
                   "k": k}
            for m in metrics_of(bench, "per_layer", cell["name"]):
                value = read_layer_metric(m["name"], ctx)
                if value is not None:
                    result["metrics"][m["name"]] = {"value": value,
                                                    "unit": m["unit"]}
            result["device"]["busy_s"] = trace["busy_s"]
            result["device"]["window_s"] = trace["window_s"]
            result["breakdown"] = {"device_ops": trace["device_ops"],
                                   "idle_gaps": trace["idle_gaps"]}
            phases = {ph: read_layer_metric(ph, ctx) or 0.0 for ph in
                      ("host_ms", "queue_wait_ms", "device_phase_ms")}
            note(programs=trace["programs"],
                 latency_accounting=dict(
                     phases, client_mean_ms=float(lat_ms.mean()),
                     outside_the_handler_ms=float(lat_ms.mean())
                     - sum(phases.values())))
        else:
            for m in metrics_of(bench, "end_to_end", cell["name"]):
                result["metrics"][m["name"]] = {
                    "value": end_to_end[m["name"]], "unit": m["unit"]}
        if args.rehearse or args.control:
            # a rehearsal or a control is never a result
            result.update(checks_passed=result["correct"], correct=False,
                          rehearsal=args.rehearse, control=args.control,
                          end_to_end=end_to_end)
        print(json.dumps(result), flush=True)
        return 0
    finally:
        if server is not None:
            server.end(timeout=30)
        for worker in workers:
            worker.end(timeout=5)
        shutil.rmtree(tmp, ignore_errors=True)


def read_layer_metric(name: str, ctx: dict):
    """A per-layer metric's own reader: ``layer_metrics/<name>.json`` is a
    Prometheus delta over the window, ``<name>.py`` has ``read(ctx)``. A
    reader that finds nothing to read returns None."""
    base = os.path.join(HERE, "layer_metrics", name)
    if os.path.exists(base + ".py"):
        return load_module(base + ".py", "layer_" + name.replace("-", "_")
                           ).read(ctx)
    with open(base + ".json") as f:
        spec = json.load(f)
    if spec["kind"] != "prom_mean":
        raise ValueError(f"layer metric {name}: unknown kind {spec['kind']}")
    delta = {part: ctx["after"].total(spec["series"] + "_" + part,
                                      spec["labels"])
             - ctx["before"].total(spec["series"] + "_" + part,
                                   spec["labels"])
             for part in ("sum", "count")}
    if delta["count"] <= 0:
        return None
    return delta["sum"] / delta["count"] * spec["scale"]


if __name__ == "__main__":
    sys.exit(main())
