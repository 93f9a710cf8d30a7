"""The load generator: HTTP/1.1 keep-alive clients on asyncio, one
thread, driving ``POST /v1/infer`` with bodies encoded before the window.

Every request leaves a ``Record`` with its due time (open loop), send
time, the time its response's last byte arrived, its status and its raw
body, all on ``time.monotonic`` — the clock the server's own spans use,
since both processes run on one host.  Bodies are parsed only after the
window, so parsing never delays a send.

* Closed loop: ``clients`` connections, each sending its next request
  when the last is answered.  Once every client has had a response
  (the pipeline is full) and ``ramp_s`` more have passed, the window
  opens at the next response and closes at the first response at or
  after ``seconds`` later.  Both edges fall on the first response of a
  batch, so the responses in (open, close] are whole batches and the
  rate carries no edge error of up to a batch.
* Open loop: request i is due at ``start + due_i`` whatever the server
  does.  It is sent on an idle connection, a new one if none is idle,
  and its latency counts from the due time, so a late send is not
  hidden.  The window is the schedule's ``seconds``.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import Dict, List, Optional, Sequence, Tuple

Key = Tuple[int, int]


@dataclasses.dataclass
class Record:
    key: Key
    n_images: int
    t_due: float
    t_send: float = 0.0
    t_done: Optional[float] = None
    status: Optional[int] = None
    raw: bytes = b""
    error: Optional[str] = None
    served_by: Optional[str] = None
    logits: Optional[list] = None

    def parse(self) -> None:
        """Fill ``served_by`` and ``logits`` from a 200 response."""
        if self.status != 200:
            return
        try:
            resp = json.loads(self.raw)
        except ValueError:
            return
        self.served_by = resp.get("served_by")
        self.logits = resp.get("logits")


def request_bytes(host: str, port: int, body: bytes) -> bytes:
    head = (f"POST /v1/infer HTTP/1.1\r\nHost: {host}:{port}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n")
    return head.encode("latin-1") + body


class Conn:
    """One keep-alive connection; one request at a time."""

    def __init__(self, reader, writer):
        self.reader, self.writer = reader, writer

    @classmethod
    async def open(cls, host: str, port: int) -> "Conn":
        return cls(*await asyncio.open_connection(host, port,
                                                  limit=1 << 24))

    async def call(self, raw: bytes, rec: Record) -> None:
        """Send, read the whole response; stamps ``rec``."""
        self.writer.write(raw)
        await self.writer.drain()
        line = await self.reader.readline()
        if not line:
            raise ConnectionError("server closed the connection")
        status = int(line.split()[1])
        length = 0
        close = False
        while True:
            h = await self.reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            k, _, v = h.decode("latin-1").partition(":")
            k = k.strip().lower()
            if k == "content-length":
                length = int(v.strip())
            elif k == "connection":
                close = v.strip().lower() == "close"
        rec.raw = await self.reader.readexactly(length) if length else b""
        rec.t_done = time.monotonic()
        rec.status = status
        if close:
            raise ConnectionResetError("server asked to close")

    async def close(self) -> None:
        self.writer.close()
        try:
            await self.writer.wait_closed()
        except (ConnectionError, OSError):
            pass


@dataclasses.dataclass
class Outcome:
    records: List[Record]
    window: Tuple[float, float]
    first_send: float
    backlog: List[Tuple[float, int]] = dataclasses.field(
        default_factory=list)


class ClosedWindow:
    """The closed loop's window: it opens at the first response at or
    after ``ramp_s`` past the moment every client has had one, and
    closes at the first response at or after ``seconds`` later."""

    def __init__(self, clients: int, seconds: float, ramp_s: float):
        self.answered = [0] * clients
        self.seconds, self.ramp_s = seconds, ramp_s
        self.ready = self.open = self.close = None

    def done(self, client: int, t: float) -> bool:
        """Note a response; True once the window has closed."""
        self.answered[client] += 1
        if self.ready is None:
            if all(self.answered):
                self.ready = t + self.ramp_s
        elif self.open is None:
            if t >= self.ready:
                self.open = t
        elif self.close is None and t >= self.open + self.seconds:
            self.close = t
        return self.close is not None


async def closed_loop(host: str, port: int, plans: Sequence[Sequence[Key]],
                      bodies: Dict[Key, bytes], seconds: float,
                      ramp_s: float = 1.0, drain_s: float = 60.0
                      ) -> Outcome:
    raws = {k: request_bytes(host, port, b) for k, b in bodies.items()}
    conns = [await Conn.open(host, port) for _ in plans]
    records: List[Record] = []
    win = ClosedWindow(len(plans), seconds, ramp_s)
    stop = asyncio.Event()

    async def client(c: int) -> None:
        plan, j = plans[c], 0
        while not stop.is_set():
            key = plan[j % len(plan)]
            j += 1
            rec = Record(key, key[0], t_due=time.monotonic())
            rec.t_send = rec.t_due
            records.append(rec)
            try:
                await conns[c].call(raws[key], rec)
            except (ConnectionError, OSError, ValueError,
                    asyncio.IncompleteReadError) as e:
                rec.error = repr(e)
                stop.set()
                return
            if win.done(c, rec.t_done):
                stop.set()

    first = time.monotonic()
    tasks = [asyncio.ensure_future(client(c)) for c in range(len(plans))]
    try:
        await asyncio.wait_for(asyncio.gather(*tasks),
                               timeout=seconds * 4 + drain_s)
    except asyncio.TimeoutError:
        pass
    finally:
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        for c in conns:
            await c.close()
    if win.close is None:
        win.close = time.monotonic()
        win.open = win.open or first
    return Outcome(records, (win.open, win.close), first)


async def open_loop(host: str, port: int,
                    schedule: Sequence[Tuple[float, Key]],
                    bodies: Dict[Key, bytes], seconds: float,
                    warm_conns: int = 32, drain_s: float = 60.0,
                    sample_every_s: float = 0.05) -> Outcome:
    raws = {k: request_bytes(host, port, b) for k, b in bodies.items()}
    idle: List[Conn] = [await Conn.open(host, port)
                        for _ in range(warm_conns)]
    records = [Record(key, key[0], t_due=t) for t, key in schedule]
    pending = set()
    backlog: List[Tuple[float, int]] = []
    inflight = [0]

    async def one(rec: Record) -> None:
        conn = idle.pop() if idle else None
        try:
            if conn is None:
                conn = await Conn.open(host, port)
            rec.t_send = time.monotonic()
            await conn.call(raws[rec.key], rec)
            idle.append(conn)
        except (ConnectionError, OSError, ValueError,
                asyncio.IncompleteReadError) as e:
            rec.error = repr(e)
            if conn is not None:
                await conn.close()
        finally:
            inflight[0] -= 1

    async def sampler(end: float) -> None:
        while time.monotonic() < end:
            backlog.append((time.monotonic(), inflight[0]))
            await asyncio.sleep(sample_every_s)

    start = time.monotonic() + 0.01
    samp = asyncio.ensure_future(sampler(start + seconds))
    for rec in records:
        rec.t_due += start
        delay = rec.t_due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        inflight[0] += 1
        task = asyncio.ensure_future(one(rec))
        pending.add(task)
        task.add_done_callback(pending.discard)
    if pending:
        await asyncio.wait(list(pending), timeout=drain_s)
    for task in list(pending):
        task.cancel()
    await asyncio.gather(*pending, samp, return_exceptions=True)
    for c in idle:
        await c.close()
    first = min((r.t_send for r in records if r.t_send), default=start)
    return Outcome(records, (start, start + seconds), first, backlog)
