"""MySQL wire client of the benchmark: handshake as root with an empty
password, COM_QUERY, text result sets. The harness's own copy of
`tools/bench_serve.py` `MiniClient` (PR 13), so that a later change to the
program's tools cannot change what the benchmark times."""

from __future__ import annotations

import socket
import struct


class WireError(RuntimeError):
    """An ERR packet from the server (errno, message)."""

    def __init__(self, errno: int, message: str):
        super().__init__(f"server error {errno}: {message}")
        self.errno = errno


class MiniClient:
    def __init__(self, host: str, port: int, timeout: float = 600.0):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._rbuf = b""
        self._handshake()

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
        resp += b"root\x00" + b"\x00"  # user, zero-length auth response
        resp += b"mysql_native_password\x00"
        self._write_packet(resp, self._seq)
        pkt = self._read_packet()
        if pkt[:1] == b"\xff":
            raise ConnectionError(f"auth failed: {pkt[3:].decode('utf8', 'replace')}")

    @staticmethod
    def _err(pkt: bytes) -> WireError:
        errno = struct.unpack_from("<H", pkt, 1)[0]
        return WireError(errno, pkt[9:].decode("utf8", "replace"))

    def query_rows(self, sql: str) -> list[tuple]:
        """COM_QUERY -> every row as a tuple of text values (None for
        NULL); an OK packet (no result set) reads as no rows."""
        self._write_packet(b"\x03" + sql.encode("utf8"), 0)
        pkt = self._read_packet()
        first = pkt[0]
        if first == 0xFF:
            raise self._err(pkt)
        if first == 0x00:
            return []
        ncols, _ = self._read_lenc(pkt, 0)
        for _ in range(ncols):
            self._read_packet()  # column definitions
        self._read_packet()  # EOF
        out: list[tuple] = []
        while True:
            pkt = self._read_packet()
            if pkt[0] == 0xFE and len(pkt) < 9:
                return out
            if pkt[0] == 0xFF:
                raise self._err(pkt)
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
            return struct.unpack("<I", buf[pos + 1:pos + 4] + b"\x00")[0], pos + 4
        return struct.unpack_from("<Q", buf, pos + 1)[0], pos + 9

    def close(self) -> None:
        try:
            self._write_packet(b"\x01", 0)  # COM_QUIT
        except OSError:
            pass
        self.sock.close()
