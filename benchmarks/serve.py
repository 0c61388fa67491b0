"""The system under test, in a process of its own: the only one that
touches JAX and the chip. A ``weaviate_tpu.server.Server`` with the default
``ServerConfig`` (telemetry off: there is no network) on loopback ports.

stdout, one JSON object per line: first ``{"ready": ..., "rest", "grpc",
"device"}``, then one reply per command read from stdin:

    describe <collection>   resident arrays of the collection's store
    trace_start <dir>       jax.profiler.start_trace
    trace_stop              jax.profiler.stop_trace
    trace_reduce            the stopped trace's numbers (trace_reduce.py)
    stats                   peak device memory
    stop                    Server.stop(), exit

A run that finds no TPU exits with code 2 before it prints anything,
unless ``--rehearse`` lets the CPU walk the same code."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def say(obj) -> None:
    print(json.dumps(obj), flush=True)


def describe(server, collection: str) -> dict:
    """What the collection's first shard keeps on the device, for the
    kernel costs: every device array the default vector's store holds, by
    its attribute's name, with shape and dtype; the store's settings that
    kernel_costs.py reads. The one place the benchmark looks inside the
    program: no endpoint gives shapes."""
    import jax

    shard = next(iter(server.db.collections[collection].shards.values()))
    index = shard.vector_indexes[""]
    store = index.store
    arrays = {name: {"shape": list(arr.shape), "dtype": str(arr.dtype)}
              for name, arr in sorted(vars(store).items())
              if isinstance(arr, jax.Array)}
    return {"index": type(index).__name__, "store": type(store).__name__,
            "arrays": arrays, "capacity": int(store.capacity),
            "rescore": getattr(store, "rescore", None),
            "rescore_limit": getattr(store, "rescore_limit", None),
            "selection": getattr(store, "selection", None)}


def install_fault(name: str) -> None:
    """Test only (tests/test_broken_path.py): break the timed path where
    answers are produced. ``shift_ids``: every coalesced dispatch hands
    each request the ids of its neighbour's slot order, rolled by one, with
    the distances left in place."""
    import numpy as np

    from weaviate_tpu.runtime.query_batcher import QueryBatcher

    if name != "shift_ids":
        raise ValueError(f"unknown fault {name!r}")
    deliver = QueryBatcher._deliver

    def broken(coal, ids, dists, t1):
        return deliver(coal, np.roll(np.asarray(ids), 1, axis=-1), dists, t1)

    QueryBatcher._deliver = staticmethod(broken)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--fault", default="")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"serve: JAX found no TPU (platform {device['platform']!r})",
              file=sys.stderr)
        return 2

    from weaviate_tpu.config import ServerConfig
    from weaviate_tpu.runtime.compile_cache import cache_dir
    from weaviate_tpu.server import Server

    if args.fault:
        install_fault(args.fault)
    server = Server(ServerConfig(data_path=args.data_dir, rest_port=0,
                                 grpc_port=0, disable_telemetry=True)).start()
    say({"ready": True, "pid": os.getpid(), "rest": server.rest.address,
         "grpc": server.grpc.port, "device": device,
         "compile_cache_dir": cache_dir()})
    tracing_to = traced_to = None
    try:
        for line in sys.stdin:
            cmd, _, arg = line.strip().partition(" ")
            if cmd == "describe":
                say(describe(server, arg))
            elif cmd == "trace_start":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(arg, profiler_options=opts)
                tracing_to = arg
                say({"tracing": True})
            elif cmd == "trace_stop":
                jax.profiler.stop_trace()
                traced_to, tracing_to = tracing_to, None
                say({"tracing": False})
            elif cmd == "trace_reduce":
                import trace_reduce

                say(trace_reduce.reduce_file(
                    trace_reduce.find_xplane(traced_to)))
            elif cmd == "stats":
                stats = devices[0].memory_stats() or {}
                say({"memory_peak_bytes": stats.get("peak_bytes_in_use")})
            elif cmd == "stop":
                break
            else:
                say({"error": f"unknown command {cmd!r}"})
    finally:
        if tracing_to is not None:
            jax.profiler.stop_trace()
        server.stop()
    say({"stopped": True})
    return 0


if __name__ == "__main__":
    sys.exit(main())
