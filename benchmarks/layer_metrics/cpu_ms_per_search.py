"""CPU the server burns a Search, all threads: the delta of
``weaviate_tpu_thread_cpu_seconds_total`` over every role (the kernel's
own account of each thread of the process, read from ``/proc/self/task``
at the scrape: runtime/tailboard.py ThreadAccount) over the Searches of
the window (``request_phase_seconds_count``, the count
``batch_occupancy`` reads), in ms. ``edge_cpu_ms``, ``pool_cpu_ms`` and
``dispatch_cpu_ms`` are parts of it. None where the program keeps no
such account, as the parent does not."""

CPU = "weaviate_tpu_thread_cpu_seconds_total"
CLOCK = "weaviate_tpu_scrape_clock_seconds"
SEARCHES = "weaviate_tpu_request_phase_seconds_count"
SEARCH_LABELS = {"operation": "grpc.search", "phase": "queue_wait"}
BUCKETS = "weaviate_tpu_query_batcher_compile_bucket_total"


def has(page, name) -> bool:
    return any(n == name for n, _, _ in page.series)


def moved(ctx, name, labels=None) -> float:
    return (ctx["after"].total(name, labels)
            - ctx["before"].total(name, labels))


def cpu_seconds(ctx, roles=None):
    """CPU seconds the roles' threads used over the window (every role:
    None); None on a page without the account."""
    if not (has(ctx["before"], CPU) and has(ctx["after"], CPU)):
        return None
    if roles is None:
        return moved(ctx, CPU)
    return sum(moved(ctx, CPU, {"role": r}) for r in roles)


def per(ctx, roles, count):
    """ms of the roles' CPU a unit of ``count`` (a delta)."""
    used = cpu_seconds(ctx, roles)
    if used is None or count <= 0:
        return None
    return used / count * 1000.0


def searches(ctx) -> float:
    return moved(ctx, SEARCHES, SEARCH_LABELS)


def dispatches(ctx) -> float:
    return moved(ctx, BUCKETS)


def read(ctx):
    return per(ctx, None, searches(ctx))
