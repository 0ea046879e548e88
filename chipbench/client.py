"""The load generator's HTTP side: ONE thread drives every request over
non-blocking sockets (``selectors``), so that offering load costs the
host little and the same whatever the number of streams.

A request is sent when it is due (open loop) or when its client's previous
request has completed (closed loop: ``chains``).  Every token event of a
streamed ``POST /generate`` is stamped with the host clock as it is read;
the terminal ``done`` event brings the served tokens and the program's own
timing ``breakdown``.  Times are ``time.monotonic()`` seconds."""

from __future__ import annotations

import heapq
import json
import selectors
import socket
import threading
import time
from collections import deque
from typing import Dict, List, Optional


class _Conn:
    """One in-flight request: de-chunks the HTTP body and splits SSE frames."""

    def __init__(self, sock, rec):
        self.sock, self.rec = sock, rec
        self.buf = b""
        self.body = b""
        self.headers_done = False
        self.status: Optional[int] = None
        self.chunked = False
        self.finished = False

    def feed(self, data: bytes, now: float) -> None:
        self.buf += data
        if not self.headers_done:
            end = self.buf.find(b"\r\n\r\n")
            if end < 0:
                return
            head, self.buf = self.buf[:end], self.buf[end + 4:]
            lines = head.split(b"\r\n")
            self.status = int(lines[0].split()[1])
            self.chunked = any(
                l.lower().startswith(b"transfer-encoding") and b"chunked" in
                l.lower() for l in lines[1:])
            self.headers_done = True
        if self.chunked:
            while True:
                eol = self.buf.find(b"\r\n")
                if eol < 0:
                    break
                size = int(self.buf[:eol] or b"0", 16)
                if len(self.buf) < eol + 2 + size + 2:
                    break
                self.body += self.buf[eol + 2:eol + 2 + size]
                self.buf = self.buf[eol + 2 + size + 2:]
                if size == 0:
                    self.finished = True
                    break
        else:
            self.body += self.buf
            self.buf = b""
        if self.status == 200 and self.chunked:
            while b"\n\n" in self.body:
                frame, self.body = self.body.split(b"\n\n", 1)
                self._event(frame, now)

    def _event(self, frame: bytes, now: float) -> None:
        kind, payload = "message", {}
        for line in frame.decode("utf-8", "replace").splitlines():
            if line.startswith("event:"):
                kind = line[6:].strip()
            elif line.startswith("data:"):
                payload = json.loads(line[5:].strip())
        rec = self.rec
        if kind == "token":
            rec["token_t"].append(now)
        elif kind == "done":
            rec["tokens"] = payload.get("tokens")
            rec["breakdown"] = payload.get("breakdown")
            rec["finish"] = payload.get("finish_reason")
            rec["done_t"] = now
        elif kind == "error":
            rec["error"] = f"{payload.get('type')}: {payload.get('error')}"
            rec["done_t"] = now

    def eof(self, now: float) -> None:
        """The server closed: a non-streamed or error reply ends here."""
        rec = self.rec
        if rec["done_t"] is not None:
            return
        if self.status == 200 and not self.chunked:
            payload = json.loads(self.body or b"{}")
            rec["tokens"] = payload.get("tokens")
            rec["breakdown"] = payload.get("breakdown")
            rec["finish"] = payload.get("finish_reason")
            rec["token_t"] = [now]
        else:
            rec["error"] = f"http {self.status}: {self.body[:200]!r}"
        rec["done_t"] = now


class LoadClient(threading.Thread):
    """``submit(request, due)`` from any thread; ``records`` afterwards."""

    def __init__(self, host: str, port: int,
                 chains: Optional[Dict[str, List[dict]]] = None):
        super().__init__(name="chipbench-load", daemon=True)
        self.addr = (host, port)
        self.records: Dict[str, dict] = {}
        self._chains = {c: deque(v) for c, v in (chains or {}).items()}
        self._chain_open = True
        self._heap: list = []
        self._lock = threading.Lock()
        self._sel = selectors.DefaultSelector()
        self._halt = threading.Event()
        self._n = 0

    def submit(self, req: dict, due: Optional[float] = None) -> None:
        """Send ``req`` at monotonic time ``due`` (None = now)."""
        body = json.dumps({
            "tokens": req["tokens"], "max_new_tokens": req["max_new_tokens"],
            "stream": req["stream"]}).encode()
        rec = {"id": req["id"], "client": req["client"],
               "counts_ttft": req["counts_ttft"],
               "prompt_len": len(req["tokens"]), "prompt": req["tokens"],
               "max_new_tokens": req["max_new_tokens"], "due": due,
               "sent": None, "token_t": [], "tokens": None,
               "breakdown": None, "finish": None, "error": None,
               "done_t": None}
        with self._lock:
            self.records[req["id"]] = rec
            self._n += 1
            heapq.heappush(self._heap,
                           (due if due is not None else 0.0, self._n, rec,
                            body))

    def close_chains(self) -> None:
        """No client starts another request from now on."""
        self._chain_open = False

    def _send(self, rec: dict, body: bytes) -> None:
        now = time.monotonic()
        if rec["due"] is None:
            rec["due"] = now
        rec["sent"] = now
        try:
            sock = socket.create_connection(self.addr, timeout=10)
            sock.sendall(
                b"POST /generate HTTP/1.1\r\nHost: bench\r\n"
                b"Content-Type: application/json\r\nContent-Length: "
                + str(len(body)).encode() + b"\r\n\r\n" + body)
            sock.setblocking(False)
        except OSError as e:
            rec["error"], rec["done_t"] = f"send: {e!r}", time.monotonic()
            return
        self._sel.register(sock, selectors.EVENT_READ, _Conn(sock, rec))

    def _close(self, conn: _Conn) -> None:
        try:
            self._sel.unregister(conn.sock)
        except (KeyError, ValueError):
            pass
        try:
            conn.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        conn.sock.close()

    def _next_of(self, client: str) -> None:
        chain = self._chains.get(client)
        if chain and self._chain_open:
            self.submit(chain.popleft(), None)

    def run(self) -> None:
        while not self._halt.is_set():
            now = time.monotonic()
            ready = []
            with self._lock:
                while self._heap and self._heap[0][0] <= now:
                    ready.append(heapq.heappop(self._heap))
                nxt = self._heap[0][0] if self._heap else now + 0.05
            for _, _, rec, body in ready:
                self._send(rec, body)
            timeout = max(0.0, min(nxt - time.monotonic(), 0.05))
            for key, _ in self._sel.select(timeout):
                conn: _Conn = key.data
                try:
                    data = conn.sock.recv(1 << 16)
                except BlockingIOError:
                    continue
                except OSError:
                    data = b""
                now = time.monotonic()
                if data:
                    conn.feed(data, now)
                if not data or conn.finished \
                        or conn.rec["done_t"] is not None:
                    conn.eof(now)
                    self._close(conn)
                    self._next_of(conn.rec["client"])
        for key in list(self._sel.get_map().values()):
            self._close(key.data)   # hang up: the server cancels the rest
        self._sel.close()

    def stop(self) -> None:
        self._halt.set()
        self.join(30)
