"""Server entry point: ``python -m weaviate_tpu`` (or weaviate_tpu.server).

Reference: cmd/weaviate-server/main.go → configure_api.go:456 — assemble
config, auth, modules, DB, cluster, REST + gRPC + metrics listeners, then
serve until signaled. Single-node by default; RAFT_JOIN with >1 member
boots the cluster path (gossip + Raft + internal data plane), mirroring
the reference's startupRoutine ordering.
"""

from __future__ import annotations

import logging
import os
import signal
import threading

from weaviate_tpu.config import ServerConfig

logger = logging.getLogger("weaviate_tpu.server")

VERSION = "0.1.0"


class Server:
    """Owns every subsystem; ``start()`` returns once listeners are up
    (tests drive it in-process), ``serve_forever()`` blocks."""

    def __init__(self, config: ServerConfig | None = None):
        self.config = config or ServerConfig.from_env()
        self._stop = threading.Event()
        self.node = None
        self.db = None
        self.rest = None
        self.grpc = None
        self.telemeter = None
        self.metrics_server = None

    # -- assembly (configure_api.go:456 ordering) -------------------------

    def start(self) -> "Server":
        cfg = self.config
        self._setup_logging()

        # multi-host data plane first (before anything touches jax):
        # with DCN_COORDINATOR_ADDRESS set, jax.devices() spans every
        # host and all meshes/collectives go global (SURVEY §5 comms)
        from weaviate_tpu.parallel.mesh import maybe_initialize_distributed

        if maybe_initialize_distributed():
            logger.info("joined multi-host JAX runtime")

        # persistent XLA compilation cache (shared helper — the offline
        # tools and bulk builds need the same warm starts as the server)
        import jax

        from weaviate_tpu.runtime.compile_cache import (cache_dir,
                                                        ensure_compile_cache)

        ensure_compile_cache()
        devices = jax.devices()
        logger.info("devices: platform=%s device_kind=%s count=%d "
                    "compile_cache=%s", devices[0].platform,
                    devices[0].device_kind, len(devices), cache_dir())

        from weaviate_tpu.auth import AuthConfig, AuthStack
        from weaviate_tpu.modules import default_provider

        auth_cfg = AuthConfig.from_env()
        auth = None
        if not auth_cfg.anonymous_enabled or auth_cfg.api_keys or \
                auth_cfg.oidc_enabled or auth_cfg.admin_users or \
                auth_cfg.readonly_users:
            auth = AuthStack(auth_cfg)

        # always constructed: the device budget may come from allocator
        # stats alone (TPU rigs report bytes_limit with zero config), so
        # gating must not hinge on any HBM_* env being set — no budget
        # discoverable means check_device_alloc is a no-op anyway
        from weaviate_tpu.runtime import MemoryMonitor

        memwatch = MemoryMonitor(
            host_limit_bytes=cfg.memory_limit_bytes or None,
            device_limit_bytes=cfg.hbm_device_limit_bytes or None,
            high_watermark=cfg.hbm_high_watermark,
            low_watermark=cfg.hbm_low_watermark)

        # device mesh for the serving stack: on a multi-host runtime
        # (or a WEAVIATE_TPU_VIRTUAL_HOSTS pod) collections row-shard
        # over the hierarchical ('host','ici') mesh so the two-level
        # ICI+DCN merge serves queries; single-process single-host
        # keeps the existing single-device placement (mesh=None)
        from weaviate_tpu.parallel.mesh import (default_mesh,
                                                is_multiprocess,
                                                virtual_hosts)

        mesh = (default_mesh()
                if is_multiprocess() or (virtual_hosts() or 1) > 1
                else None)
        if mesh is not None:
            logger.info("serving over %s mesh: %s",
                        "hierarchical" if "host" in mesh.axis_names
                        else "1-D", dict(mesh.shape))

        cluster_mode = len(cfg.raft_join) > 1 or bool(cfg.cluster_join)
        if cluster_mode:
            from weaviate_tpu.cluster.node import ClusterNode

            peers = cfg.raft_join or [cfg.cluster_hostname]
            self.node = ClusterNode(cfg.cluster_hostname, cfg.data_path,
                                    raft_peers=peers, host=cfg.host,
                                    port=cfg.cluster_data_port,
                                    advertise=cfg.cluster_advertise or None,
                                    remote_timeout=cfg.remote_rpc_timeout_s,
                                    sync_wal=cfg.wal_sync, mesh=mesh)
            self.node.start(seed_addrs=cfg.cluster_join or None)
            self.db = self.node.db
        else:
            from weaviate_tpu.db.database import Database

            self.db = Database(cfg.data_path,
                               local_node=cfg.cluster_hostname,
                               start_cycles=True,
                               memory_monitor=memwatch,
                               async_indexing=cfg.async_indexing or None,
                               sync_wal=cfg.wal_sync, mesh=mesh)

        # tailboard wiring: incident flight-recorder snapshots land in
        # the data dir; explicit SLO config (if any) replaces defaults
        from weaviate_tpu.runtime import tailboard

        tailboard.configure(data_dir=cfg.data_path,
                            enabled=cfg.tailboard_enabled,
                            slos_json=cfg.slo_config or None)

        # kernelscope wiring: on-demand kernel captures persist under
        # <data_dir>/kernelscope, pruned to the last PROFILING_KEEP
        from weaviate_tpu.runtime import kernelscope

        kernelscope.configure(data_dir=cfg.data_path,
                              keep=cfg.profile_keep)

        # driftwatch wiring: history ring + self-sealed live baseline
        # live under <data_dir>/driftwatch; the cycle itself is
        # registered by Database (start_cycles=True here runs it)
        from weaviate_tpu.runtime import driftwatch

        driftwatch.configure(data_dir=cfg.data_path,
                             enabled=cfg.driftwatch_enabled,
                             interval=cfg.drift_interval_s)

        modules = default_provider(self.db, enabled=cfg.enabled_modules)

        # FROZEN tenant tier: ship offloaded tenants through a backup
        # backend (reference: offload-s3 module + tenantactivity FROZEN)
        offload_name = os.environ.get("OFFLOAD_BACKEND", "")
        if offload_name:
            self.db.set_offload_backend(modules.backup_backend(offload_name))

        from weaviate_tpu.api.rest import RestServer

        if self.node is not None:
            self.rest = self.node.serve_rest(
                host=cfg.host, port=cfg.rest_port, modules=modules,
                auth=auth, query_deadline_s=cfg.query_deadline_s)
        else:
            self.rest = RestServer(self.db, host=cfg.host,
                                   port=cfg.rest_port, modules=modules,
                                   auth=auth,
                                   query_deadline_s=cfg.query_deadline_s)
            self.rest.start()

        from weaviate_tpu.api.grpc.server import GrpcServer

        use_native_plane = False
        if os.environ.get("WEAVIATE_TPU_NATIVE_DATAPLANE") == "1" \
                and auth is None:
            from weaviate_tpu.native import dataplane as _dpn

            use_native_plane = _dpn.available()
        if use_native_plane:
            # C++ transport serves the port; the (unstarted) GrpcServer
            # donates its handler logic to the fallback path
            from weaviate_tpu.api.grpc.native_plane import NativeDataPlane

            handlers = GrpcServer(self.db, host=cfg.host, port=0,
                                  modules=modules, auth=None)
            self.grpc = NativeDataPlane(self.db, handlers, host=cfg.host,
                                        port=cfg.grpc_port).start()
            logger.info("native gRPC data plane enabled")
        else:
            self.grpc = GrpcServer(self.db, host=cfg.host,
                                   port=cfg.grpc_port,
                                   modules=modules, auth=auth).start()

        self._start_profiler(cfg.profiling_port)

        if cfg.prometheus_enabled:
            from weaviate_tpu.runtime.metrics import serve_metrics

            self.metrics_server = serve_metrics(cfg.host,
                                                cfg.prometheus_port)

        if not cfg.disable_telemetry:
            from weaviate_tpu.runtime.telemetry import Telemeter

            self.telemeter = Telemeter(self.db, version=VERSION,
                                       data_dir=cfg.data_path)
            self.telemeter.start()

        logger.info("weaviate-tpu %s serving REST on %s gRPC on :%s",
                    VERSION, self.rest.address, self.grpc.port)
        return self

    def _start_profiler(self, port: int) -> bool:
        """Start the JAX profiler server on ``port``. Returns whether a
        server was started: ``PROFILING_PORT=0`` (the default) means
        NEVER — the early return is what the config unit test pins.

        Reference: setupGoProfiling serves pprof on PROFILING_PORT
        (configure_api.go:1094); the JAX profiler server is the TPU
        analog — point TensorBoard/xprof at it for device traces.
        One-shot captures don't need this: ``GET
        /v1/debug/profile?ms=N`` runs a programmatic capture inline."""
        if not port:
            return False
        try:
            import jax

            jax.profiler.start_server(port)
            logger.info("JAX profiler server on :%s", port)
            return True
        except Exception as e:  # noqa: BLE001 — profiling is best-effort
            logger.warning("profiler server failed to start: %s", e)
            return False

    def _setup_logging(self) -> None:
        level = getattr(logging, self.config.log_level.upper(),
                        logging.INFO)
        if self.config.log_format == "json":
            import json as _json

            class JsonFormatter(logging.Formatter):
                def format(self, record):
                    return _json.dumps({
                        "level": record.levelname.lower(),
                        "msg": record.getMessage(),
                        "logger": record.name,
                        "time": self.formatTime(record),
                    })

            handler = logging.StreamHandler()
            handler.setFormatter(JsonFormatter())
            logging.basicConfig(level=level, handlers=[handler])
        else:
            logging.basicConfig(
                level=level,
                format="%(asctime)s %(levelname)s %(name)s: %(message)s")

    # -- lifecycle ---------------------------------------------------------

    def serve_forever(self) -> None:
        try:
            signal.signal(signal.SIGTERM, lambda *_: self._stop.set())
            signal.signal(signal.SIGINT, lambda *_: self._stop.set())
        except ValueError:
            pass  # not the main thread
        self._stop.wait()
        self.stop()

    def stop(self) -> None:
        self._stop.set()
        from weaviate_tpu.runtime import tailboard

        tailboard.stop_probe()
        if self.telemeter is not None:
            self.telemeter.stop()
        if self.metrics_server is not None:
            # release the monitoring port — a leaked listener makes an
            # in-process restart fail with EADDRINUSE
            self.metrics_server.shutdown()
            self.metrics_server.server_close()
            self.metrics_server = None
        if self.grpc is not None:
            self.grpc.stop()
        if self.node is not None:
            self.node.close()  # closes rest + db too
        else:
            if self.rest is not None:
                self.rest.stop()
            if self.db is not None:
                self.db.close()


def main() -> None:
    Server().start().serve_forever()


if __name__ == "__main__":
    main()
