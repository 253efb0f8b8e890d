"""The one traffic generator: reads a mix file (`traffic/<mix>.json`) and
drives closed-loop statement streams over the MySQL wire, each stream
sending its next statement as soon as the last has answered.

A mix is data: `templates` (SQL text with `{param}` holes, the reference
class that answers it, and the columns it must read), and `streams`, each
an ordered list of (template, params). The literals are part of the mix,
never drawn from the seed: every program key of the system under test
holds its literals, so a new literal is a new XLA compile (PERF.md,
"Facts of the program"). `--seed` decides the data and nothing of the
traffic: every seed sends the same texts in the same order.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

from .wire import MiniClient


@dataclass(frozen=True)
class Statement:
    stream: int
    template: str
    reference: str
    params: dict
    sql: str
    rows_read: int  # table rows a completed execution has read
    bytes_needed: int  # narrowest bytes a scan of those rows must read


@dataclass
class Sent:
    """One statement as the client saw it."""
    stmt: Statement
    t_send_ns: int
    t_done_ns: int = 0
    rows: list | None = None
    error: str | None = None


def scaled_rows(table: dict, rows_scale: float) -> int:
    """A table's rows in this run: the configuration's, cut by the dry run's scale."""
    return max(int(table["rows"] * rows_scale), 2)


def build_streams(mix: dict, config: dict, rows_scale: float = 1.0) -> list[list[Statement]]:
    rows = {t["name"]: scaled_rows(t, rows_scale) for t in config["tables"]}
    out = []
    for i, stream in enumerate(mix["streams"]):
        stmts = []
        for s in stream["statements"]:
            tpl = mix["templates"][s["template"]]
            per_row = bytes_per_row(mix, config, s["template"])
            rows_read = sum(rows[t] for t in per_row)
            bytes_needed = sum(rows[t] * b for t, b in per_row.items())
            stmts.append(Statement(i, s["template"], tpl["reference"], s["params"],
                                   tpl["sql"].format(**s["params"]), rows_read, bytes_needed))
        out.append(stmts)
    texts = [s.sql for st in out for s in st]
    if len(set(texts)) != len(texts):
        raise ValueError("a statement text appears twice in the mix: the batcher would "
                         "deduplicate concurrent identical tasks")
    return out


def bytes_per_row(mix: dict, config: dict, template: str) -> dict[str, int]:
    """Needed bytes per row of each table a template reads."""
    tables = {t["name"]: t for t in config["tables"]}
    return {
        tname: sum(tables[tname]["narrowest_bytes"][c] for c in cols)
        for tname, cols in mix["templates"][template]["reads"].items()
    }


@dataclass
class Streams:
    """Open connections, one a stream, with the configuration's session
    variables set on each."""
    port: int
    session_vars: dict
    streams: list[list[Statement]]
    clients: list[MiniClient] = field(default_factory=list)

    def connect(self) -> None:
        for _ in self.streams:
            c = MiniClient("127.0.0.1", self.port)
            for k, v in self.session_vars.items():
                c.query_rows(f"SET {k} = {v}")
            self.clients.append(c)

    def close(self) -> None:
        for c in self.clients:
            c.close()
        self.clients.clear()

    def _one(self, i: int, stmt: Statement) -> Sent:
        sent = Sent(stmt, time.perf_counter_ns())
        try:
            sent.rows = self.clients[i].query_rows(stmt.sql)
        except Exception as e:  # noqa: BLE001 — a failed statement is a counted result
            sent.error = f"{type(e).__name__}: {e}"
        sent.t_done_ns = time.perf_counter_ns()
        return sent

    def warm_alone(self, only: list[Statement] | None = None) -> list[Sent]:
        """Every text once (or `only` those given), one at a time."""
        stmts = only if only is not None else [s for st in self.streams for s in st]
        return [self._one(s.stream, s) for s in stmts]

    def run(self, deadline_ns: int) -> list[Sent]:
        """All streams at once, each on its own thread and connection,
        through its list again and again until `deadline_ns` on the
        perf_counter clock (no statement is sent after it; one in flight
        is waited for)."""
        results: list[list[Sent]] = [[] for _ in self.streams]

        def loop(i: int) -> None:
            st = self.streams[i]
            n = 0
            while time.perf_counter_ns() < deadline_ns:
                results[i].append(self._one(i, st[n % len(st)]))
                n += 1

        threads = [threading.Thread(target=loop, args=(i,), name=f"stream-{i}")
                   for i in range(len(self.streams))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return [s for r in results for s in r]
