"""Protocol client of the benchmark: standard library only.

A copy of what ``presto_tpu/client.py`` does on the wire (POST
``/v1/statement``, follow ``nextUri`` to the last page, one keep-alive
connection, session properties in ``X-Trino-Session``), kept here so
that the load generator imports neither JAX nor ``presto_tpu`` and a
later change to the program's client cannot move the yardstick.

An empty poll (the statement is queued, or running with no page ready)
sleeps a tenth of the statement's age, at least 2 ms and at most the
program client's 20 ms: a reading overshoots by at most that sleep, so
a 10 ms statement is not rounded up to 20 and a 14 s one is not polled
7,000 times.
"""

from __future__ import annotations

import http.client
import json
import socket
import time
from urllib.parse import quote, urlsplit

POLL_MIN_S, POLL_MAX_S, POLL_SHARE = 0.002, 0.020, 0.1


class StatementError(Exception):
    """The server answered a statement with an error, or not at all."""


class Connection:
    """One keep-alive HTTP/1.1 connection; not shared between threads."""

    def __init__(self, base_url: str, session: dict | None = None,
                 user: str = "bench", timeout_s: float = 120.0):
        sp = urlsplit(base_url)
        self._host, self._port = sp.hostname, sp.port
        self._timeout_s = timeout_s
        self._headers = {"X-Trino-User": user}
        if session:
            self._headers["X-Trino-Session"] = ",".join(
                f"{k}={quote(str(v))}" for k, v in session.items())
        self._conn: http.client.HTTPConnection | None = None

    def _open(self) -> http.client.HTTPConnection:
        conn = http.client.HTTPConnection(self._host, self._port,
                                          timeout=self._timeout_s)
        conn.connect()
        # request and response ping-pong on this socket: Nagle with
        # delayed ACK would add about 40 ms to every exchange
        conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return conn

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def _request(self, method: str, path: str,
                 body: bytes | None = None) -> dict:
        if self._conn is None:
            self._conn = self._open()
        try:
            self._conn.request(method, path, body=body,
                               headers=self._headers)
            resp = self._conn.getresponse()
            data = resp.read()
        except (http.client.HTTPException, OSError) as exc:
            # never sent twice: a POSTed INSERT could run twice
            self.close()
            raise StatementError(f"{method} {path}: "
                                 f"{type(exc).__name__}: {exc}") from exc
        if resp.status >= 400 and resp.status != 429:
            self.close()
            raise StatementError(f"{method} {path}: HTTP {resp.status}")
        try:
            return json.loads(data or b"{}")
        except ValueError as exc:
            self.close()
            raise StatementError(f"{method} {path}: body is not JSON") \
                from exc

    def execute(self, sql: str) -> tuple[str, list]:
        """Run one statement to its last page: (query id, rows)."""
        t0 = time.monotonic()
        out = self._request("POST", "/v1/statement", sql.encode())
        qid = str(out.get("id", ""))
        rows: list = []
        while True:
            if out.get("error"):
                err = out["error"]
                raise StatementError(
                    f"{err.get('errorName', 'ERROR')}: "
                    f"{err.get('message', 'failed')}")
            data = out.get("data")
            if data:
                rows.extend(data)
            next_uri = out.get("nextUri")
            if next_uri is None:
                return qid, rows
            if not data:
                age = time.monotonic() - t0
                if age > self._timeout_s:
                    raise StatementError(
                        f"no last page after {self._timeout_s:g} s")
                time.sleep(min(POLL_MAX_S,
                               max(POLL_MIN_S, POLL_SHARE * age)))
            sp = urlsplit(next_uri)
            out = self._request(
                "GET", sp.path + (f"?{sp.query}" if sp.query else ""))
