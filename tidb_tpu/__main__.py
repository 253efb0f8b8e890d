"""Process entry — `python -m tidb_tpu` starts the MySQL-protocol server
(ref: tidb-server/main.go:157 main, :505 setGlobalVars, :621 createServer;
flags subset + graceful signal shutdown)."""

from __future__ import annotations

import argparse
import logging
import signal
import sys
import threading


def load_config(path: str) -> dict:
    """TOML config file (ref: config/config.go + config.toml.example —
    the file layer below CLI flags). Recognized keys mirror the flag
    names; [log]/[security]/[gc] tables flatten into them."""
    import tomllib

    with open(path, "rb") as f:
        raw = tomllib.load(f)
    flat: dict = {}
    for k, v in raw.items():
        if isinstance(v, dict):
            for k2, v2 in v.items():
                flat[f"{k}.{k2}"] = v2
        else:
            flat[k] = v
    out = {}
    # (dest, coerce, validator) — the same constraints the CLI flags carry
    mapping = {
        "host": ("host", str, None),
        "port": ("port", int, None),
        "log.level": ("log_level", str, ("debug", "info", "warn", "error")),
        "gc.life-minutes": ("gc_life_minutes", int, None),
        "security.enable-sem": ("enable_sem", bool, None),
    }
    for src, (dst, coerce, choices) in mapping.items():
        if src not in flat:
            continue
        try:
            v = coerce(flat[src])
        except (TypeError, ValueError):
            raise SystemExit(f"config: {src} must be {coerce.__name__}, got {flat[src]!r}")
        if choices is not None and v not in choices:
            raise SystemExit(f"config: {src} must be one of {choices}, got {v!r}")
        out[dst] = v
    unknown = sorted(set(flat) - set(mapping))
    if unknown:
        logging.getLogger(__name__).warning("config: ignoring unknown keys %s", unknown)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="tidb-tpu-server", description="TPU-native TiDB-compatible SQL server")
    ap.add_argument("--config", default=None, help="TOML config file (flags override it)")
    ap.add_argument("--host", default=None, help="listen address")
    ap.add_argument("-P", "--port", type=int, default=None, help="listen port (0 = ephemeral)")
    ap.add_argument("--log-level", default=None, choices=["debug", "info", "warn", "error"])
    ap.add_argument("--gc-life-minutes", type=int, default=None, help="MVCC GC retention window")
    ap.add_argument(
        "--enable-sem", action="store_true", default=None,
        help="security enhanced mode: hide restricted vars/tables, deny FILE (ref: util/sem)",
    )
    ap.add_argument("--data-dir", default=None,
                    help="durable store directory (omit for in-memory)")
    ap.add_argument(
        "--wal-spare-dirs", default=None,
        help="comma-separated spare WAL dirs for online media failover "
             "(tidb_wal_spare_dirs; requires --data-dir)",
    )
    args = ap.parse_args(argv)
    # precedence: defaults < config file < CLI flags (tidb-server rule)
    defaults = {"host": "127.0.0.1", "port": 4000, "log_level": "info",
                "gc_life_minutes": 10, "enable_sem": False}
    conf = dict(defaults)
    if args.config:
        conf.update(load_config(args.config))
    for k in defaults:
        v = getattr(args, k)
        if v is not None:
            conf[k] = v
        setattr(args, k, conf[k])
    if args.enable_sem:
        from .utils import sem

        sem.enable()

    logging.basicConfig(
        level={"debug": logging.DEBUG, "info": logging.INFO, "warn": logging.WARNING, "error": logging.ERROR}[args.log_level],
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
    )
    from .server import Server

    storage = None
    if args.data_dir:
        from .storage.txn import Storage

        spares = [p.strip() for p in (args.wal_spare_dirs or "").split(",") if p.strip()]
        storage = Storage(data_dir=args.data_dir, spare_dirs=spares or None)
        if spares:
            storage.global_vars["tidb_wal_spare_dirs"] = ",".join(spares)
    srv = Server(storage=storage, host=args.host, port=args.port)
    srv.storage.gc_worker.life_ms = args.gc_life_minutes * 60 * 1000
    port = srv.start()
    print(f"tidb-tpu server listening on {args.host}:{port}", flush=True)

    stop = threading.Event()

    def on_signal(signum, frame):  # noqa: ARG001
        print("shutting down...", flush=True)
        srv.close()
        stop.set()

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    import time as _t

    last_gc = _t.time()
    while not stop.is_set():
        stop.wait(30)
        # background GC loop honoring the LIVE tidb_gc_run_interval
        # (leaderTick; a SET GLOBAL takes effect on the next wakeup)
        if _t.time() - last_gc >= srv.storage.gc_worker.interval_ms / 1000.0:
            srv.storage.gc_worker.tick()
            last_gc = _t.time()
    return 0


if __name__ == "__main__":
    sys.exit(main())
