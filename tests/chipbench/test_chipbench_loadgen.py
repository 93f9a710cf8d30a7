"""The traffic generator and the load generator's two loops, against a
fake HTTP server."""
import asyncio
import json
import threading
import time

import numpy as np
import pytest

from chipbench import loadgen, spec, traffic

CFG = {"channels": 3, "img": 4}


def test_open_schedule_has_the_same_work_for_every_seed():
    mix = spec.traffic("vgg16-224.online")
    a = traffic.open_schedule(mix, 1, 10.0, rate_rps=30.0)
    b = traffic.open_schedule(mix, 2 ** 40 + 1, 10.0, rate_rps=30.0)
    assert len(a) == len(b) == 300
    assert sorted(k[0] for _, k in a) == sorted(k[0] for _, k in b)
    def gaps(s):        # every inter-arrival gap, the one to the end too
        return sorted(np.round(np.diff([t for t, _ in s] + [10.0]), 9))
    assert gaps(a) == gaps(b)
    assert [k for _, k in a] != [k for _, k in b]
    for s in (a, b):
        ts = [t for t, _ in s]
        assert ts[0] == 0.0 and ts == sorted(ts) and ts[-1] < 10.0
    counts = {n: sum(1 for _, k in a if k[0] == n) for n in range(1, 9)}
    probs = traffic.size_probs(mix)
    for n, c in counts.items():
        assert abs(c - 300 * probs[n]) <= 1
    assert sum(n * c for n, c in counts.items()) / 300 == pytest.approx(
        sum(n * p for n, p in probs.items()), rel=0.01)


def test_images_come_from_the_seed():
    x = traffic.pool_images(7, (2, 1), CFG)
    assert x.shape == (2, 3, 4, 4) and x.dtype == np.float32
    assert np.array_equal(x, traffic.pool_images(7, (2, 1), CFG))
    assert not np.array_equal(x, traffic.pool_images(2 ** 33 + 7, (2, 1),
                                                     CFG))
    body = json.loads(traffic.body(x))
    assert body["shape"] == [2, 3, 4, 4] and body["dtype"] == "float32"


def test_closed_plan_cycles_the_whole_pool():
    mix = spec.traffic("vgg16-224.bulk")
    plans = traffic.closed_plan(mix, 3)
    assert len(plans) == mix["clients"]
    for p in plans:
        assert sorted(p) == sorted(traffic.pool_keys(mix))


class FakeServer:
    """Answers ``POST /v1/infer`` after ``delay``, or, with ``tick``,
    all waiting requests together every ``tick`` seconds (a batch)."""

    def __init__(self, delay=0.0, tick=None):
        self.delay, self.tick = delay, tick
        self.loop = asyncio.new_event_loop()
        self.server = self.loop.run_until_complete(asyncio.start_server(
            self._conn, "127.0.0.1", 0))
        self.port = self.server.sockets[0].getsockname()[1]
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()

    async def _conn(self, reader, writer):
        try:
            while True:
                line = await reader.readline()
                if not line:
                    break
                length = 0
                while True:
                    h = await reader.readline()
                    if h in (b"\r\n", b""):
                        break
                    k, _, v = h.decode().partition(":")
                    if k.strip().lower() == "content-length":
                        length = int(v)
                await reader.readexactly(length)
                if self.tick:
                    now = time.monotonic()
                    await asyncio.sleep(self.tick - now % self.tick)
                else:
                    await asyncio.sleep(self.delay)
                body = b'{"served_by": "primary", "logits": [[0.0]]}'
                writer.write(b"HTTP/1.1 200 OK\r\nContent-Length: %d\r\n\r\n"
                             % len(body) + body)
                await writer.drain()
        finally:
            writer.close()

    async def _shutdown(self):
        self.server.close()
        await asyncio.wait_for(self.server.wait_closed(), 5)

    def close(self):
        asyncio.run_coroutine_threadsafe(self._shutdown(),
                                         self.loop).result(10)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(5)
        assert not self.thread.is_alive()
        self.loop.close()


@pytest.fixture
def bodies():
    return {(1, 0): b"{}"}


def test_open_loop_times_from_the_due_time_and_shows_lateness(bodies):
    srv = FakeServer(delay=0.03)
    sched = [(0.01 * i, (1, 0)) for i in range(10)]

    async def main():
        # a 0.1 s stall of the generator's own loop at 0.02 s
        asyncio.get_running_loop().call_later(0.02, time.sleep, 0.1)
        return await loadgen.open_loop("127.0.0.1", srv.port, sched,
                                       bodies, 0.1, warm_conns=2)
    try:
        out = asyncio.run(main())
    finally:
        srv.close()
    recs = out.records
    assert all(r.status == 200 for r in recs)
    assert all(r.t_send >= r.t_due for r in recs)
    late = [r.t_send - r.t_due for r in recs]
    assert max(late) > 0.05                     # the stall shows
    for r in recs:                              # and the latency keeps it
        assert r.t_done - r.t_due >= (r.t_send - r.t_due) + 0.03
    assert out.window[1] - out.window[0] == pytest.approx(0.1)
    assert out.first_send >= out.window[0]


@pytest.mark.parametrize("phase", [0.0, 0.013, 0.049])
def test_closed_window_holds_whole_batches(phase):
    """Four clients answered in bursts every 0.05 s: whatever the phase
    of the ramp, the responses in (open, close] are whole bursts over
    whole periods."""
    win = loadgen.ClosedWindow(4, 0.5, 0.1 + phase)
    times = []
    for k in range(40):
        for c in range(4):
            t = 0.05 * k + 1e-4 * c
            times.append(t)
            if win.done(c, t):
                break
        if win.close is not None:
            break
    inside = [t for t in times if win.open < t <= win.close]
    assert len(inside) % 4 == 0
    assert len(inside) / (win.close - win.open) == pytest.approx(80.0)


def test_closed_loop_runs_its_window(bodies):
    srv = FakeServer(tick=0.05)
    plans = [[(1, 0)]] * 4
    try:
        out = asyncio.run(loadgen.closed_loop("127.0.0.1", srv.port, plans,
                                              bodies, 0.5, ramp_s=0.1))
    finally:
        srv.close()
    lo, hi = out.window
    assert hi - lo >= 0.5
    assert all(r.status == 200 for r in out.records)
    inside = [r for r in out.records if r.t_done and lo < r.t_done <= hi]
    assert len(inside) / (hi - lo) == pytest.approx(4 / 0.05, rel=0.2)


@pytest.mark.parametrize("backlog,holds", [
    ([1, 2, 1, 2, 1, 2, 2, 1], True), ([1, 2, 3, 5, 8, 12, 17, 23], False)])
def test_sweep_judges_a_growing_backlog(backlog, holds):
    from chipbench import sweep
    recs = [loadgen.Record((1, 0), 1, t_due=0.1 * i, t_send=0.1 * i,
                           t_done=0.1 * i + 0.01, status=200)
            for i in range(8)]
    out = loadgen.Outcome(recs, (0.0, 0.8), 0.0,
                          [(0.1 * i + 0.05, n) for i, n in enumerate(backlog)])
    row = sweep.judge(out, 10.0, 0.8)
    assert row["holds"] is holds
    assert row["p95_ms"] == pytest.approx(10.0)
