"""The load generator: seeded schedules, the open loop's due-time
origin (a stall is charged to every request due during it) and the
closed loop's outstanding requests, against a stub HTTP server on a
local port."""

import asyncio
import json
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from bench_tiny import ROOT


def test_poisson_schedule_is_fixed_work_per_seed():
    from bench import inputs

    a = inputs.poisson_schedule(2**40 + 1, 250.0, 4.0)
    b = inputs.poisson_schedule(2**40 + 1, 250.0, 4.0)
    c = inputs.poisson_schedule(2**40 + 2, 250.0, 4.0)
    assert len(a) == len(c) == 1000 and np.array_equal(a, b)
    assert a[-1] == pytest.approx(4.0) and c[-1] == pytest.approx(4.0)
    assert np.all(np.diff(a) > 0) and not np.array_equal(a, c)


class StubServer:
    """Answers every :predict with int32 zeros after `delay` seconds;
    holds every answer that comes due inside [stall_from, stall_to) until
    stall_to; counts the requests it holds at once."""

    def __init__(self, delay: float = 0.0):
        self.stall_from = self.stall_to = float("inf")
        self.delay = delay
        self.held = self.held_max = 0
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever, daemon=True)
        self.thread.start()
        self.server = asyncio.run_coroutine_threadsafe(
            asyncio.start_server(self.handle, "127.0.0.1", 0), self.loop).result(10)
        self.port = self.server.sockets[0].getsockname()[1]

    async def handle(self, reader, writer):
        while True:
            line = await reader.readline()
            if not line:
                break
            length = 0
            while (h := await reader.readline()) not in (b"\r\n", b""):
                name, _, value = h.decode().partition(":")
                if name.lower() == "content-length":
                    length = int(value)
            body = await reader.readexactly(length)
            self.held += 1
            self.held_max = max(self.held_max, self.held)
            await asyncio.sleep(self.delay)
            now = time.perf_counter()
            if self.stall_from <= now < self.stall_to:
                await asyncio.sleep(self.stall_to - now)
            n = len(body) // (4 * 16)
            payload = np.zeros(n, "<i4").tobytes()
            self.held -= 1
            writer.write(b"HTTP/1.1 200 OK\r\nContent-Type: application/x-hdc-i32\r\n"
                         + f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload)
            await writer.drain()
        writer.close()

    def close(self):
        self.server.close()
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)


def _spec(tmp_path, port, **kw):
    spec = {"host": "127.0.0.1", "port": port, "model": "m", "seed": 3,
            "seconds": 1.2, "traffic": {"arrival": "poisson", "rate_per_s": 200.0},
            "pool": 32, "n_features": 16, "images_per_request": 1, "connections": 2,
            "timeout_s": 30.0, "loop": "open", "out": str(tmp_path / "out.npz")}
    spec.update(kw)
    path = tmp_path / "spec.json"
    path.write_text(json.dumps(spec))
    return path


def _drive(spec_path, on_go=None):
    child = subprocess.Popen([sys.executable, str(ROOT / "bench/loadgen.py"), str(spec_path)],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        assert child.stdout.readline().strip() == "ready"
        t_go = time.perf_counter()
        if on_go is not None:
            on_go(t_go)
        child.stdin.write("go\n")
        child.stdin.flush()
        summary = json.loads(child.stdout.readline())
        assert child.wait(timeout=60) == 0
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(10)
    return summary


def test_open_loop_charges_a_stall_to_every_request_due_in_it(tmp_path):
    server = StubServer()
    try:
        stall = {}

        def on_go(t_go):
            stall["from"], stall["to"] = t_go + 0.4, t_go + 0.7
            server.stall_from, server.stall_to = stall["from"], stall["to"]

        summary = _drive(_spec(tmp_path, server.port), on_go)
    finally:
        server.close()
    res = np.load(tmp_path / "out.npz")
    assert summary["requests"] == 240 and (res["status"] == 200).all()
    lat = res["done"] - res["due"]
    during = (res["due"] > stall["from"] + 0.02) & (res["due"] < stall["to"] - 0.05)
    assert during.sum() > 20
    # timed from when it was due: each waits at least for the stall to end
    assert np.all(lat[during] >= (stall["to"] - res["due"][during]) - 0.005)
    # with two connections both held, the generator itself ran late, and
    # that lateness is inside the latency, not hidden by a send-time origin
    late = res["sent"] - res["due"]
    assert late[during].max() > 0.1 and summary["late_max_ms"] > 100


def test_closed_loop_keeps_every_connection_busy_and_answers_all(tmp_path):
    server = StubServer(delay=0.01)
    try:
        summary = _drive(_spec(tmp_path, server.port, loop="closed", seconds=0.6,
                               traffic={}, pool=64, images_per_request=8,
                               connections=6))
    finally:
        server.close()
    res = np.load(tmp_path / "out.npz")
    n = summary["requests"]
    assert summary["ok"] == n and (res["status"] == 200).all()
    assert (res["labels"] == 0).all() and len(res["labels"]) == n
    # exactly `connections` out at once, as the server saw it too
    assert summary["outstanding_max"] == 6 and server.held_max == 6
    assert n >= 6 * 0.6 / 0.011 * 0.5
    for c in range(6):
        mine = np.flatnonzero(res["conn"] == c)
        assert len(mine) > 1
        # one request at a time on a connection, the next sent on the answer
        assert np.all(res["sent"][mine[1:]] >= res["done"][mine[:-1]])
        assert np.all(res["sent"][mine[1:]] - res["done"][mine[:-1]] < 0.05)
    # nothing sent after the window's time; blocks cycle through the pool
    assert res["sent"].max() < float(res["t0"]) + 0.6
    assert np.array_equal(res["block"][np.argsort(res["sent"])], np.arange(n) % 8)
