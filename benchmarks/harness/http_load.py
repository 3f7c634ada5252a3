"""An open-loop HTTP client for ``POST /generate`` streams: one thread,
non-blocking sockets, every request sent when it is DUE whether or not
earlier ones have finished, every streamed token stamped on arrival.
(``bench.py:_sec_lm_serve_frontdoor`` speaks the same protocol from four
waiting clients; this is that surface made open-loop.)"""

from __future__ import annotations

import dataclasses
import json
import selectors
import socket
import time
from typing import Callable, List, Optional


@dataclasses.dataclass
class Outcome:
    planned: object
    sent_s: Optional[float] = None  # all times relative to the window
    first_s: Optional[float] = None
    last_s: Optional[float] = None
    end_s: Optional[float] = None
    status: Optional[int] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    arrivals: List[float] = dataclasses.field(default_factory=list)
    done: Optional[dict] = None
    error: Optional[str] = None


class _Stream:
    """One response being read: status line, headers, then chunks, each
    chunk one JSON line."""

    def __init__(self, outcome: Outcome):
        self.outcome = outcome
        self.buf = b""
        self.in_body = False

    def feed(self, data: bytes, now_s: float) -> bool:
        """Returns True once the stream has ended."""
        self.buf += data
        out = self.outcome
        if not self.in_body:
            head, sep, rest = self.buf.partition(b"\r\n\r\n")
            if not sep:
                return False
            out.status = int(head.split(b" ", 2)[1])
            self.in_body, self.buf = True, rest
            if out.status != 200:
                out.error = f"HTTP {out.status}"
        if out.status != 200:
            return False  # read on to the close
        while True:
            size_line, sep, rest = self.buf.partition(b"\r\n")
            if not sep:
                return False
            size = int(size_line, 16)
            if size == 0:
                return True
            if len(rest) < size + 2:
                return False
            record = json.loads(rest[:size])
            self.buf = rest[size + 2:]
            if "token" in record:
                if out.first_s is None:
                    out.first_s = now_s
                out.last_s = now_s
                out.tokens.append(int(record["token"]))
                out.arrivals.append(now_s)
            elif record.get("done"):
                out.done = record


def run_open_loop(port: int, plan, t_open: float, deadline_s: float,
                  until_s: float, on_tick: Optional[Callable] = None
                  ) -> List[Outcome]:
    """Send every planned request at ``t_open + due_s`` (``t_open`` on
    ``time.perf_counter``'s clock) and read every stream to its end, or
    until ``until_s`` seconds after the window opened.  ``on_tick(now_s)``
    is called once a loop turn, for the tracer's timetable."""
    outcomes = [Outcome(p) for p in sorted(plan, key=lambda p: p.due_s)]
    selector = selectors.DefaultSelector()
    to_send, live = 0, 0
    while True:
        now_s = time.perf_counter() - t_open
        if on_tick is not None:
            on_tick(now_s)
        while to_send < len(outcomes) and outcomes[to_send].planned.due_s <= now_s:
            out = outcomes[to_send]
            to_send += 1
            body = json.dumps(
                {
                    "prompt": out.planned.prompt,
                    "max_new_tokens": out.planned.max_new_tokens,
                    "deadline_s": deadline_s,
                }
            ).encode()
            try:
                sock = socket.create_connection(("127.0.0.1", port), timeout=5)
                sock.sendall(
                    b"POST /generate HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                    b"Content-Type: application/json\r\nContent-Length: "
                    + str(len(body)).encode() + b"\r\n\r\n" + body
                )
                sock.setblocking(False)
            except OSError as exc:
                out.error = f"send failed: {exc}"
                out.end_s = time.perf_counter() - t_open
                continue
            out.sent_s = time.perf_counter() - t_open
            selector.register(sock, selectors.EVENT_READ, _Stream(out))
            live += 1
        if to_send == len(outcomes) and live == 0:
            break
        if now_s > until_s:
            break
        wait = 0.05
        if to_send < len(outcomes):
            wait = min(wait, max(outcomes[to_send].planned.due_s - now_s, 0.0))
        for key, _ in selector.select(timeout=wait):
            stream, sock = key.data, key.fileobj
            now_s = time.perf_counter() - t_open
            try:
                data = sock.recv(1 << 16)
            except BlockingIOError:
                continue
            except OSError as exc:
                data, stream.outcome.error = b"", f"read failed: {exc}"
            if not data or stream.feed(data, now_s):
                stream.outcome.end_s = now_s
                selector.unregister(sock)
                sock.close()
                live -= 1
    for key in list(selector.get_map().values()):
        key.data.outcome.error = key.data.outcome.error or "unfinished"
        selector.unregister(key.fileobj)
        key.fileobj.close()
    selector.close()
    return outcomes
