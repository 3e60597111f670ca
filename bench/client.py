"""Open-loop load generator and SSE client, run as a child process.

It imports nothing but the standard library, so it never loads JAX: the
parent holds the chip and its engine pump thread, and this process does
not compete with them for the parent's interpreter lock.

Protocol: one JSON object on standard input,

    {"host", "port", "t0": <time.monotonic() at window open>,
     "seconds": <window length>,
     "requests": [{"rid", "due", "payload"}, ...]}

then one JSON object on standard output when the window has closed:

    {"requests": [{"rid", "sent", "status", "recv": [...], "tokens": [...],
                   "end": <terminal event or null>, "end_t"}, ...]}

Each request is sent when it is due (`t0 + due`), whatever the state of
earlier ones. `recv` holds the time each token frame was read off the
socket, on the same monotonic clock as `t0`. At `t0 + seconds` every
connection still open is closed, which the server takes as a client
disconnect.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time
from typing import Any, Dict, List, Tuple

TERMINAL = ("finish", "shed", "cancel", "evicted", "error", "shutdown")


class SSEParser:
    """Incremental server-sent-events decoder: bytes in, (event, data)
    out, with frames split anywhere across reads."""

    def __init__(self):
        self._buf = b""
        self._event = ""
        self._data: List[str] = []

    def feed(self, chunk: bytes) -> List[Tuple[str, Dict[str, Any]]]:
        self._buf += chunk
        out = []
        while True:
            nl = self._buf.find(b"\n")
            if nl < 0:
                return out
            line = self._buf[:nl].rstrip(b"\r").decode("utf-8")
            self._buf = self._buf[nl + 1:]
            if line == "":
                if self._event or self._data:
                    raw = "\n".join(self._data)
                    out.append((self._event or "message",
                                json.loads(raw) if raw else {}))
                self._event, self._data = "", []
            elif not line.startswith(":"):
                field, _, value = line.partition(":")
                value = value[1:] if value.startswith(" ") else value
                if field == "event":
                    self._event = value
                elif field == "data":
                    self._data.append(value)


def request_bytes(host: str, payload: Dict[str, Any]) -> bytes:
    body = json.dumps(payload).encode("utf-8")
    head = (f"POST /v1/stream HTTP/1.1\r\nHost: {host}\r\n"
            "Connection: close\r\nContent-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n")
    return head.encode("latin-1") + body


async def one(host: str, port: int, t0: float, req: Dict[str, Any],
              rec: Dict[str, Any]) -> None:
    """Send one request at its due time and record its frames."""
    delay = t0 + req["due"] - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    rec["sent"] = time.monotonic()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(request_bytes(host, req["payload"]))
        await writer.drain()
        buf = b""
        while b"\r\n\r\n" not in buf:
            chunk = await reader.read(65536)
            if not chunk:
                rec["status"] = -1
                return
            buf += chunk
        head, _, rest = buf.partition(b"\r\n\r\n")
        rec["status"] = int(head.split(b" ", 2)[1])
        if rec["status"] != 200:
            return
        parser = SSEParser()
        chunk = rest
        while True:
            now = time.monotonic()
            for kind, data in parser.feed(chunk):
                if kind == "token":
                    rec["recv"].append(now)
                    rec["tokens"].append(data["token"])
                elif kind in TERMINAL:
                    rec["end"], rec["end_t"] = kind, now
                    return
            chunk = await reader.read(65536)
            if not chunk:
                return
    except OSError as e:
        rec["status"] = -1
        rec["error"] = repr(e)
    finally:
        if writer is not None:
            writer.close()


async def drive(spec: Dict[str, Any]) -> List[Dict[str, Any]]:
    t0, t_end = spec["t0"], spec["t0"] + spec["seconds"]
    recs = [{"rid": r["rid"], "sent": None, "status": None, "recv": [],
             "tokens": [], "end": None, "end_t": None}
            for r in spec["requests"]]
    tasks = [asyncio.ensure_future(one(spec["host"], spec["port"], t0, r, rec))
             for r, rec in zip(spec["requests"], recs)]
    await asyncio.sleep(max(0.0, t_end - time.monotonic()))
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return recs


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    recs = asyncio.run(drive(spec))
    sys.stdout.write(json.dumps({"requests": recs}) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
