"""The interpreter's account (ISSUE 39; runtime/tailboard.py, point 6):
CPU by thread role read from a procfs tree, the lock probe, CPU beside
wall on a dispatch side, ``off_cpu`` of a staged request. All on stated
clocks and a fake ``/proc``: nothing here sleeps for its verdict."""

import os
import threading
import time

import pytest

from weaviate_tpu.runtime import metrics, tailboard


# -- (a) threads by role over a fake /proc ------------------------------------


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def _stat_line(tid, comm, ticks):
    """A ``stat`` line whose comm holds a space and a bracket, as a
    thread's may: utime is the 14th field, stime the 15th."""
    return (f"{tid} ({comm}) S 1 1 1 0 -1 4194304 0 0 0 0 "
            f"{ticks} 0 0 0 20 0 1 0 0 0 0\n")


class _Proc:
    """A process directory the account can walk: ``thread`` writes one
    task's files, ``gone`` removes it, ``total`` states the process's
    own utime (ticks)."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path / "proc")
        self.tick = os.sysconf("SC_CLK_TCK")
        self.total(0)

    def total(self, seconds):
        _write(self.dir + "/stat",
               _stat_line(1, "python3", int(seconds * self.tick)))

    def thread(self, tid, comm, cpu_s, wait_s=0.0, schedstat=True):
        base = f"{self.dir}/task/{tid}/"
        _write(base + "comm", comm + "\n")
        _write(base + "stat",
               _stat_line(tid, comm + " (x)", int(cpu_s * self.tick)))
        if schedstat:
            _write(base + "schedstat",
                   f"{int(cpu_s * 1e9)} {int(wait_s * 1e9)} 17\n")

    def gone(self, tid):
        base = f"{self.dir}/task/{tid}/"
        for name in os.listdir(base):
            os.unlink(base + name)
        os.rmdir(base)


PYTHON_NAMES = [
    ("Thread-1 (_serve)", "grpc_serve"),
    ("grpc-pool_7", "grpc_pool"),
    ("query-batcher", "batcher_worker"),
    ("dp-dispatch", "batcher_worker"),
    ("qb-transfer", "batcher_drain"),
    ("d2h-transfer", "batcher_drain"),
    ("cyclemanager", "cyclemanager"),
    ("rest-8080", "rest"),
    ("Thread-12 (process_request_thread)", "rest"),
    ("MainThread", "python_other"),
    ("lock-probe", "python_other"),
    ("Sift-search_0", "python_other"),
]
# the comms a serving process showed on the chip's host (PERF.md section
# 6, PR 39), and some it did not: a prefix nobody saw names no role
NATIVE_COMMS = [
    ("grpc_global_tim", "grpc_core"),
    ("event_engine", "grpc_core"),
    ("lifeguard", "grpc_core"),
    ("tf_XLAEigen", "device_runtime"),
    ("llvm-worker-3", "device_runtime"),
    ("pjrt-tpu-tasks", "device_runtime"),
    ("pjrt-tpu-blocki", "device_runtime"),
    ("tfrt-non-blocki", "device_runtime"),
    ("EventFDAsyncWor", "device_runtime"),
    ("futex-default-S", "device_runtime"),
    ("python3", "native_other"),
    ("something new", "native_other"),
    ("async_io", "native_other"),
    ("sparse_worker", "native_other"),
    ("", "native_other"),
]


@pytest.mark.parametrize("name,role", PYTHON_NAMES)
def test_a_python_thread_is_told_by_its_name(name, role):
    # the comm says nothing for a Python thread (3.12 does not set it)
    assert tailboard.thread_role(name, "grpc_global_tim") == role
    assert role in tailboard.THREAD_ROLES


@pytest.mark.parametrize("comm,role", NATIVE_COMMS)
def test_a_native_thread_is_told_by_its_comm(comm, role):
    assert tailboard.thread_role(None, comm) == role
    assert role in tailboard.THREAD_ROLES


def _read_plain(path):
    try:
        with open(path, "rb") as f:
            return f.read(512)
    except OSError:
        return b""


class _Closing:
    """An account whose every walk is held to the process's own total:
    all that the roles were charged so far adds up to it."""

    def __init__(self, proc, read=None):
        self.proc = proc
        self.acct = tailboard.ThreadAccount(proc.dir, read=read)
        self.charged = 0.0

    def walk(self, names, total=None):
        cpu, wait, live = self.acct.walk(names)
        assert all(v >= 0.0 for v in cpu.values())
        assert all(v >= 0.0 for v in wait.values())
        self.charged += sum(cpu.values())
        if total is not None:
            assert self.charged == pytest.approx(total)
        return cpu, wait, live


@pytest.mark.parametrize("reader", ["procfs", "plain"])
def test_walk_maps_python_threads_by_native_id_and_native_by_comm(
        tmp_path, reader):
    proc = _Proc(tmp_path)
    proc.thread(101, "python3", 1.5, 0.25)       # a Python thread
    proc.thread(102, "python3", 0.5)             # native, comm inherited
    proc.thread(103, "event_engine", 0.125)
    proc.thread(104, "python3", 2.0, 1.0)
    proc.total(4.125)
    acct = _Closing(proc, None if reader == "procfs" else _read_plain)
    names = {101: "grpc-pool_0", 104: "query-batcher"}
    cpu, wait, live = acct.walk(names, total=4.125)
    assert set(cpu) == set(wait) == set(live) == set(tailboard.THREAD_ROLES)
    assert cpu["grpc_pool"] == pytest.approx(1.5)
    assert wait["grpc_pool"] == pytest.approx(0.25)
    assert cpu["native_other"] == pytest.approx(0.5)
    assert cpu["grpc_core"] == pytest.approx(0.125)
    assert cpu["batcher_worker"] == pytest.approx(2.0)
    assert wait["batcher_worker"] == pytest.approx(1.0)
    assert live["grpc_pool"] == live["grpc_core"] == 1
    assert sum(live.values()) == 4
    assert cpu["exited"] == 0.0
    # a second walk gives what was used since the first
    proc.thread(101, "python3", 1.75, 0.25)
    proc.total(4.375)
    cpu, wait, _ = acct.walk(names, total=4.375)
    assert cpu["grpc_pool"] == pytest.approx(0.25)
    assert wait["grpc_pool"] == 0.0
    assert cpu["batcher_worker"] == 0.0


def test_a_thread_that_vanishes_leaves_its_total_and_a_reused_id_starts_anew(
        tmp_path):
    proc = _Proc(tmp_path)
    proc.thread(7, "python3", 3.0)
    proc.thread(8, "python3", 1.0)
    proc.total(4.0)
    acct = _Closing(proc)
    names = {7: "grpc-pool_0", 8: "grpc-pool_1"}
    assert acct.walk(names, total=4.0)[0]["grpc_pool"] == pytest.approx(4.0)
    # the thread goes, the process's total keeps what it used: that was
    # charged to its role while it lived and is charged to nobody again
    proc.gone(7)
    cpu, _, live = acct.walk(names, total=4.0)
    assert cpu["grpc_pool"] == 0.0 and live["grpc_pool"] == 1
    assert cpu["exited"] == 0.0
    # the id comes back under a thread that has used less than the old
    # one had: all of it is new, nothing is taken away
    proc.thread(7, "python3", 0.5)
    proc.total(4.5)
    cpu, _, _ = acct.walk(names, total=4.5)
    assert cpu["grpc_pool"] == pytest.approx(0.5)
    assert cpu["exited"] == 0.0


def test_a_seen_threads_last_stretch_is_exited_and_nothing_of_it_twice(
        tmp_path):
    """A thread read at one walk runs on for 0.25 s and ends before the
    next: what was read of it stays in its role, the rest is ``exited``,
    and the roles still add up to the process, walk after walk."""
    proc = _Proc(tmp_path)
    proc.thread(20, "llvm-worker-0", 2.0)
    proc.thread(21, "python3", 1.0)
    proc.total(3.0)
    acct = _Closing(proc)
    assert acct.walk({21: "qb-transfer"}, total=3.0)[0][
        "device_runtime"] == pytest.approx(2.0)
    proc.gone(20)
    proc.total(3.25)
    cpu = acct.walk({21: "qb-transfer"}, total=3.25)[0]
    assert cpu["device_runtime"] == 0.0
    assert cpu["exited"] == pytest.approx(0.25)
    # a reused id is one more thread that went: the old one's readings
    # are not in the remainder when the new one has used less ...
    proc.thread(21, "python3", 0.5)
    proc.total(3.75)
    cpu = acct.walk({21: "qb-transfer"}, total=3.75)[0]
    assert cpu["batcher_drain"] == pytest.approx(0.5)
    assert cpu["exited"] == 0.0
    # ... and walks later nothing of either comes back
    assert sum(acct.walk({21: "qb-transfer"}, total=3.75)[0].values()) == 0.0


def test_what_ended_between_two_walks_is_exited_so_the_roles_close(tmp_path):
    proc = _Proc(tmp_path)
    proc.thread(5, "python3", 1.0)
    proc.total(1.0)
    acct = _Closing(proc)
    acct.walk({5: "cyclemanager"}, total=1.0)
    # a thread lived and died unseen and used 0.75 s: the process's own
    # total has it, no task directory does
    proc.thread(5, "python3", 1.25)
    proc.total(2.0)
    cpu = acct.walk({5: "cyclemanager"}, total=2.0)[0]
    assert cpu["cyclemanager"] == pytest.approx(0.25)
    assert cpu["exited"] == pytest.approx(0.75)
    # the process's total is in ticks and may lag the threads' ns: the
    # remainder never runs backwards
    proc.thread(5, "python3", 1.30)
    assert acct.walk({5: "cyclemanager"})[0]["exited"] == 0.0


def test_stat_stands_in_where_the_kernel_keeps_no_schedstat(tmp_path):
    proc = _Proc(tmp_path)
    proc.thread(9, "tf_XLAEigen", 2.5, schedstat=False)
    proc.thread(10, "tf_XLAEigen", 0.5, schedstat=False)
    proc.total(3.0)
    asked = []

    def read(path):
        asked.append(path.rsplit("/", 1)[1])
        return _read_plain(path)
    acct = _Closing(proc, read)
    cpu, wait, _ = acct.walk({}, total=3.0)
    assert cpu["device_runtime"] == pytest.approx(3.0)
    assert wait["device_runtime"] == 0.0
    # the first thread said so: the kernel is not asked again
    assert asked.count("schedstat") == 1
    acct.walk({}, total=3.0)
    assert asked.count("schedstat") == 1


def test_a_walk_is_one_read_a_thread_once_it_knows_them(tmp_path):
    """A native thread's comm is read at its first two sightings (the
    first may come before it has named itself) and kept from then on; a
    Python thread's is never read."""
    proc = _Proc(tmp_path)
    proc.thread(30, "python3", 0.25)             # not yet named
    proc.thread(31, "python3", 0.25)             # a Python thread
    proc.total(0.5)
    asked = []

    def read(path):
        asked.append(path.rsplit("/", 2)[1:])
        return _read_plain(path)
    acct = _Closing(proc, read)
    assert acct.walk({31: "grpc-pool_0"}, total=0.5)[0][
        "native_other"] == pytest.approx(0.25)
    proc.thread(30, "pjrt-tpu-tasks", 0.5)
    proc.total(0.75)
    assert acct.walk({31: "grpc-pool_0"}, total=0.75)[0][
        "device_runtime"] == pytest.approx(0.25)
    assert asked.count(["30", "comm"]) == 2
    del asked[:]
    proc.thread(30, "pjrt-tpu-tasks", 1.0)
    proc.total(1.25)
    cpu = acct.walk({31: "grpc-pool_0"}, total=1.25)[0]
    assert cpu["device_runtime"] == pytest.approx(0.5)
    assert sorted(asked) == [["30", "schedstat"], ["31", "schedstat"],
                             ["proc", "stat"]]
    # the id under another thread: its comm is read again
    proc.thread(30, "event_engine", 0.125)
    proc.total(1.375)
    cpu = acct.walk({31: "grpc-pool_0"}, total=1.375)[0]
    assert cpu["grpc_core"] == pytest.approx(0.125)
    assert acct.acct.walk_seconds > 0.0


def _series(name):
    body = metrics.scrape()[0].decode()
    return [ln for ln in body.splitlines() if ln.startswith(name)]


def test_the_scrape_publishes_the_account_of_this_process():
    """The live tree: this thread is MainThread, so python_other has
    used CPU; every role label is one of the fixed set; the counters do
    not fall from one scrape to the next."""
    before = time.monotonic()
    first = {ln.split(" ")[0]: float(ln.split(" ")[1])
             for ln in _series("weaviate_tpu_thread_cpu_seconds_total")}
    roles = {k.split('"')[1] for k in first}
    assert roles == set(tailboard.THREAD_ROLES)
    assert first['weaviate_tpu_thread_cpu_seconds_total'
                 '{role="python_other"}'] > 0.0
    clock = float(_series("weaviate_tpu_scrape_clock_seconds")[0].split()[1])
    assert before <= clock <= time.monotonic()
    live = {ln.split('"')[1]: float(ln.split(" ")[1])
            for ln in _series("weaviate_tpu_threads{")}
    assert live["python_other"] >= 1 and set(live) == roles
    second = {ln.split(" ")[0]: float(ln.split(" ")[1])
              for ln in _series("weaviate_tpu_thread_cpu_seconds_total")}
    assert all(second[k] >= v for k, v in first.items())
    assert _series("weaviate_tpu_thread_runqueue_wait_seconds_total")
    took = float(_series(
        "weaviate_tpu_thread_account_walk_seconds")[0].split()[1])
    assert 0.0 < took < 5.0


def test_the_tailboard_off_reads_no_account(monkeypatch):
    monkeypatch.setenv("WEAVIATE_TPU_TAILBOARD", "0")
    tailboard.reset_for_tests()
    walked = []
    monkeypatch.setattr(tailboard.ThreadAccount, "walk",
                        lambda self, names: walked.append(1))
    tailboard.scrape_refresh()
    assert not walked


# -- (b) the lock probe -------------------------------------------------------


def _probe_threads():
    return [t for t in threading.enumerate() if t.name == "lock-probe"]


def test_the_probe_does_not_start_with_the_tailboard_off(monkeypatch):
    monkeypatch.setenv("WEAVIATE_TPU_TAILBOARD", "0")
    tailboard.reset_for_tests()
    tailboard.configure()
    assert tailboard._probe is None and not _probe_threads()
    tailboard.configure(enabled=False)
    assert tailboard._probe is None and not _probe_threads()


def test_the_probe_starts_once_with_the_tailboard_and_stops():
    tailboard.configure(enabled=True)
    probe = tailboard._probe
    assert probe is not None and probe.alive()
    tailboard.configure(enabled=True)          # a second server start
    assert tailboard._probe is probe and len(_probe_threads()) == 1
    tailboard.stop_probe()
    assert tailboard._probe is None
    probe._thread.join(timeout=5.0)
    assert not probe.alive()


def test_the_probes_samples_reach_the_histogram_only_at_a_scrape():
    child = metrics.interpreter_wait_seconds.labels()
    tailboard.configure(enabled=True)
    probe = tailboard._probe
    probe.stop()                               # stated samples only
    probe._thread.join(timeout=5.0)
    probe.take()
    count, total = child.count, child.total
    probe.samples.extend([0.003, 0.0001, -0.00001])
    tailboard.flush()
    assert (child.count, child.total) == (count, total)
    tailboard.scrape_refresh()
    assert child.count == count + 3
    assert child.total == pytest.approx(total + 0.0031)  # never negative
    assert not probe.samples


# -- (c) CPU beside wall on a dispatch side -----------------------------------


class _Clock:
    """A stated clock in tailboard's place of ``time``."""

    def __init__(self):
        self.now = 100.0
        self.cpu = 7.0

    def perf_counter(self):
        return self.now

    def thread_time(self):
        return self.cpu

    def step(self, wall, cpu):
        self.now += wall
        self.cpu += cpu

    monotonic = staticmethod(time.monotonic)
    time = staticmethod(time.time)


@pytest.fixture(autouse=True)
def _first_side_of_this_thread():
    """A thread stamps its CPU clock on every ``CPU_STAMP_EVERY``-th of
    its sides, the first included: every case starts at a first side."""
    tailboard._bound.__dict__.pop("sides", None)


def _fold(side):
    columns, cpu_columns = {}, {}
    tailboard._fold_side(side, columns, cpu_columns)
    return ({k[1]: v[0] for k, v in columns.items()},
            {k[1]: v[0] for k, v in cpu_columns.items()})


def _worker_side(clock, monkeypatch):
    """idle 10 ms (0.1 ms of CPU), assemble 2 ms (all CPU), launch 4 ms
    (1 ms of CPU), assemble again 1 ms (0.5 ms)."""
    monkeypatch.setattr(tailboard, "time", clock)
    rec = tailboard.new_dispatch("batcher", "flat")
    side = tailboard.bind_dispatch(rec, "worker", "assemble")
    side.mark("idle")
    clock.step(0.010, 0.0001)
    side.mark("assemble")
    clock.step(0.002, 0.002)
    with tailboard.dispatch_stage("launch"):
        clock.step(0.004, 0.001)
    clock.step(0.001, 0.0005)
    tailboard.unbind_dispatch(keep=False)
    return rec, side


def test_a_sides_marks_fold_into_cpu_a_stage_and_worker_wall(monkeypatch):
    rec, side = _worker_side(_Clock(), monkeypatch)
    wall, cpu = _fold(side)
    assert wall == pytest.approx({"idle": 0.010, "assemble": 0.003,
                                  "launch": 0.004, "worker_wall": 0.017})
    assert cpu == pytest.approx({"idle": 0.0001, "assemble": 0.0025,
                                 "launch": 0.001, "worker_wall": 0.0036})
    assert rec["worker_cpu_ms"] == pytest.approx(
        {k: v * 1000.0 for k, v in cpu.items()})
    assert set(rec["worker_cpu_ms"]) == set(rec["worker_ms"])


def test_a_thread_clock_that_moves_in_ticks_still_adds_up(monkeypatch):
    """Where the kernel moves a thread's CPU clock in ticks (the chip's
    hosts do) a stage reads nothing or a whole tick, more than its own
    wall time: the readings are kept as they are, so that over many
    dispatches they add up to what the thread used; never negative."""
    clock = _Clock()
    monkeypatch.setattr(tailboard, "time", clock)
    side = tailboard.bind_dispatch(tailboard.new_dispatch("batcher", "flat"),
                                   "drain", "finish")
    clock.step(0.001, 0.0)
    with tailboard.dispatch_stage("deliver"):
        clock.step(0.001, 0.010)               # the tick lands here
    clock.step(0.001, -0.000001)               # a clock that stepped back
    tailboard.unbind_dispatch(keep=False)
    wall, cpu = _fold(side)
    assert wall == pytest.approx({"finish": 0.002, "deliver": 0.001})
    assert cpu == pytest.approx({"finish": 0.0, "deliver": 0.010})


def test_a_nested_sides_cpu_is_its_own_and_not_the_outer_sides(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(tailboard, "time", clock)
    outer = tailboard.bind_dispatch(
        tailboard.new_dispatch("batcher", "flat"), "worker", "assemble")
    clock.step(0.001, 0.001)
    srec = tailboard.new_dispatch("batcher", "flat")
    srec["path"] = "solo"
    inner = tailboard.bind_dispatch(srec, "worker", "assemble")
    clock.step(0.005, 0.004)
    tailboard.unbind_dispatch(keep=False)
    clock.step(0.002, 0.001)
    tailboard.unbind_dispatch(keep=False)
    wall, cpu = _fold(outer)
    assert wall["worker_wall"] == pytest.approx(0.003)
    assert cpu["worker_wall"] == cpu["assemble"] == pytest.approx(0.002)
    wall, cpu = _fold(inner)
    assert wall["assemble"] == pytest.approx(0.005)
    assert cpu["assemble"] == pytest.approx(0.004)


def test_the_fold_observes_cpu_beside_wall_stage_for_stage(monkeypatch):
    stages = ("idle", "assemble", "launch", "worker_wall")

    def read():
        return [(child.count, child.total) for family in (
            metrics.dispatch_stage_seconds,
            metrics.dispatch_stage_cpu_seconds)
            for child in (family.labels("flat", s) for s in stages)]
    tailboard.flush()
    before = read()
    rec, side = _worker_side(_Clock(), monkeypatch)
    tailboard._pending_dispatch.push(side)
    tailboard.flush()
    moved = [(c - c0, t - t0) for (c, t), (c0, t0) in zip(read(), before)]
    wall, cpu = moved[:len(stages)], moved[len(stages):]
    for (n_wall, s_wall), (n_cpu, s_cpu) in zip(wall, cpu):
        assert n_cpu == n_wall == 1
        assert 0.0 <= s_cpu <= s_wall


def test_a_thread_stamps_cpu_on_one_side_in_four_and_the_counts_say_so(
        monkeypatch):
    """The clock is a system call (6 us a read on the chip's hosts), so
    the sides in between take none; the CPU family's count beside the
    wall family's tells a reader by how much to scale."""
    clock = _Clock()
    clock.reads = 0

    def thread_time():
        clock.reads += 1
        return clock.cpu
    clock.thread_time = thread_time
    walls = metrics.dispatch_stage_seconds.labels("flat", "launch").count
    cpus = metrics.dispatch_stage_cpu_seconds.labels("flat", "launch").count
    stamped = []
    for _ in range(2 * tailboard.CPU_STAMP_EVERY):
        rec, side = _worker_side(clock, monkeypatch)
        stamped.append(side.cpus is not None)
        tailboard._pending_dispatch.push(side)
    assert stamped == [True, False, False, False] * 2
    assert clock.reads == 2 * 6            # six stamps a stamped side
    tailboard.flush()
    assert metrics.dispatch_stage_seconds.labels(
        "flat", "launch").count == walls + 8
    assert metrics.dispatch_stage_cpu_seconds.labels(
        "flat", "launch").count == cpus + 2


def test_nested_sides_are_counted_apart_so_both_sorts_are_stamped(
        monkeypatch):
    """A worker whose every dispatch holds one solo dispatch alternates
    outer and nested sides: one count over both would stamp one sort."""
    monkeypatch.setattr(tailboard, "time", _Clock())
    outer, nested = [], []
    for _ in range(tailboard.CPU_STAMP_EVERY + 1):
        side = tailboard.bind_dispatch(
            tailboard.new_dispatch("batcher", "flat"), "worker", "assemble")
        inner = tailboard.bind_dispatch(
            tailboard.new_dispatch("batcher", "flat"), "worker", "assemble")
        tailboard.unbind_dispatch(keep=False)
        tailboard.unbind_dispatch(keep=False)
        outer.append(side.cpus is not None)
        nested.append(inner.cpus is not None)
    assert outer == nested == [True, False, False, False, True]


def test_kinds_are_counted_apart_so_a_shared_thread_stamps_each(monkeypatch):
    """The drain thread of several batchers closes sides of kinds that
    may alternate: every kind is stamped one side in four, so a series'
    CPU count over its wall count is the scale of that series."""
    monkeypatch.setattr(tailboard, "time", _Clock())
    stamped = {"flat": [], "bq": []}
    for _ in range(tailboard.CPU_STAMP_EVERY + 1):
        for kind in stamped:
            side = tailboard.bind_dispatch(
                tailboard.new_dispatch("batcher", kind), "drain", "finish")
            tailboard.unbind_dispatch(keep=False)
            stamped[kind].append(side.cpus is not None)
    assert stamped["flat"] == stamped["bq"] == [True, False, False, False,
                                                True]


def test_with_the_tailboard_off_a_side_takes_no_cpu_stamp(monkeypatch):
    monkeypatch.setenv("WEAVIATE_TPU_TAILBOARD", "0")
    tailboard.reset_for_tests()
    clock = _Clock()
    clock.thread_time = None                   # a call would raise
    rec, side = _worker_side(clock, monkeypatch)
    assert side.cpus is None
    wall, cpu = _fold(side)
    assert wall["worker_wall"] == pytest.approx(0.017) and cpu == {}
    assert "worker_cpu_ms" not in rec


# -- (c) a request's off_cpu --------------------------------------------------

ONE_SHARD = {"pool_wait": 0.002, "parse": 0.001, "search": 0.030,
             "wake": 0.004, "fetch": 0.002, "reply": 0.001, "send": 0.003,
             "handler_cpu": 0.0015, "server_residency": 0.037}
PHASES = {"queue_wait": 0.010, "device": 0.012, "transfer": 0.001}


def _off_cpu(phases, stages):
    values = tailboard._stage_values(phases, stages)
    names = tailboard.REQUEST_STAGES + tailboard.REQUEST_EXTRAS
    assert len(values) == len(names)
    return dict(zip(names, values))["off_cpu"]


def test_off_cpu_of_a_one_shard_request():
    # the handler ran 0.037 - 0.002 - 0.003 = 0.032; it was meant to wait
    # 0.023 and had 0.0015 of CPU
    assert _off_cpu(PHASES, ONE_SHARD) == pytest.approx(0.032 - 0.023
                                                        - 0.0015)


def test_off_cpu_of_a_fan_out_takes_the_critical_paths_waits_alone():
    """A fanned-out request is charged ONE queue_wait, device and
    transfer (the last shard's): ``fanout_wait`` and ``merge`` are time
    its own thread was meant to run, so they stay inside ``off_cpu``
    less whatever CPU they had."""
    fanned = dict(ONE_SHARD, fanout_wait=0.003, merge=0.0005)
    assert _off_cpu(PHASES, fanned) == _off_cpu(PHASES, ONE_SHARD)


@pytest.mark.parametrize("stages", [
    dict(ONE_SHARD, handler_cpu=0.5),           # CPU over the wall
    dict(ONE_SHARD, server_residency=0.001),    # a torn record
    {},                                         # nothing stamped
])
def test_off_cpu_is_never_negative(stages):
    assert _off_cpu(PHASES, stages) == 0.0
