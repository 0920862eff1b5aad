"""HTTP load generator for the serving cells, run as its own process.

    python bench/loadgen.py SPEC.json

It never imports JAX, so the chip stays with the server's process.  It
makes its images from the run's seed (`bench.inputs`, numpy only),
opens its keep-alive connections, sends one warm-up request on each,
prints ``ready`` and waits for ``go`` on standard input.  Then it runs
its loop (``spec["loop"]``), writes every request's times, status,
block, connection and labels to ``spec["out"]`` (``.npz``), and prints
one JSON summary line.

``open``: requests are due on the schedule drawn from the seed
(`inputs.schedule`), whatever the server does.  Each request's latency
runs from when it was due, not from when it was sent, so a stall is
charged to every request due during it; how late the generator sent is
reported beside it.

``closed``: each connection sends its next request the moment its last
answer arrives (a request is due then), until ``spec["seconds"]`` have
passed; the requests still out are waited for.  At most one request is
out on a connection, so at most ``connections`` in all.

The protocol is the server's raw binary hot path: a POST of C-order
little-endian float32 rows (``application/x-hdc-f32``) answered by
int32 labels (``application/x-hdc-i32``).
"""

from __future__ import annotations

import asyncio
import itertools
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import inputs  # noqa: E402

CT_F32 = "application/x-hdc-f32"
CT_I32 = "application/x-hdc-i32"


class Conn:
    """One keep-alive HTTP/1.1 connection."""

    def __init__(self, host: str, port: int, path: str, index: int):
        self.host, self.port, self.path, self.index = host, port, path, index
        self.reader = self.writer = None

    async def open(self) -> "Conn":
        self.reader, self.writer = await asyncio.open_connection(self.host, self.port)
        return self

    def close(self) -> None:
        if self.writer is not None:
            self.writer.close()
            self.writer = None

    async def post(self, body: bytes) -> tuple[int, bytes]:
        head = (
            f"POST {self.path} HTTP/1.1\r\nHost: {self.host}\r\n"
            f"Content-Type: {CT_F32}\r\nAccept: {CT_I32}\r\n"
            f"Content-Length: {len(body)}\r\n\r\n"
        ).encode()
        self.writer.write(head + body)
        status_line = await self.reader.readline()
        if not status_line:
            raise ConnectionError("connection closed by the server")
        status = int(status_line.split()[1])
        length = 0
        while True:
            line = await self.reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            name, _, value = line.decode("latin-1").partition(":")
            if name.strip().lower() == "content-length":
                length = int(value.strip())
        payload = await self.reader.readexactly(length) if length else b""
        return status, payload


def _bodies(spec: dict) -> tuple[list[bytes], int]:
    per = int(spec["images_per_request"])
    pool = inputs.images_np(spec["seed"], inputs.STREAM_POOL, spec["pool"],
                            spec["n_features"])
    n_blocks = len(pool) // per
    return [pool[b * per : (b + 1) * per].tobytes() for b in range(n_blocks)], per


class Recorder:
    FIELDS = ("due", "sent", "done", "status", "block", "conn", "labels")

    def __init__(self, n: int, per: int):
        self.n = n
        self.due = np.full(n, np.nan)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.status = np.zeros(n, np.int32)
        self.block = np.zeros(n, np.int32)
        self.conn = np.zeros(n, np.int32)
        self.labels = np.full((n, per), -1, np.int32)

    def take(self) -> int:
        """Index of one more request, growing the arrays as needed."""
        i = self.n
        self.n += 1
        if i == len(self.due):
            grown = Recorder(max(1, 2 * i), self.labels.shape[1])
            for name in self.FIELDS:
                getattr(grown, name)[:i] = getattr(self, name)
                setattr(self, name, getattr(grown, name))
        return i

    def arrays(self) -> dict:
        return {name: getattr(self, name)[: self.n] for name in self.FIELDS}

    async def one(self, i: int, conn: Conn, spec: dict, body: bytes, block: int,
                  per: int) -> Conn:
        """Send request i on `conn`; returns a usable connection."""
        self.block[i] = block
        self.conn[i] = conn.index
        self.sent[i] = time.perf_counter()
        try:
            status, payload = await asyncio.wait_for(conn.post(body), spec["timeout_s"])
        except (OSError, asyncio.TimeoutError, asyncio.IncompleteReadError, ValueError):
            self.done[i] = time.perf_counter()
            self.status[i] = -1
            conn.close()
            return await Conn(conn.host, conn.port, conn.path, conn.index).open()
        self.done[i] = time.perf_counter()
        self.status[i] = status
        if status == 200 and len(payload) == 4 * per:
            self.labels[i] = np.frombuffer(payload, "<i4")
        return conn


async def _open_loop(spec, conns, bodies, per) -> tuple[Recorder, float]:
    due = inputs.schedule(spec["traffic"], spec["seed"], spec["seconds"])
    rec = Recorder(len(due), per)
    free: asyncio.Queue = asyncio.Queue()
    for c in conns:
        free.put_nowait(c)

    async def run(i: int, conn: Conn) -> None:
        free.put_nowait(await rec.one(i, conn, spec, bodies[i % len(bodies)],
                                      i % len(bodies), per))

    tasks = []
    t0 = time.perf_counter() + 0.005
    for i, d in enumerate(due):
        rec.due[i] = t0 + d
        wait = rec.due[i] - time.perf_counter()
        if wait > 0:
            await asyncio.sleep(wait)
        conn = await free.get()
        tasks.append(asyncio.create_task(run(i, conn)))
    await asyncio.gather(*tasks)
    return rec, t0


async def _closed_loop(spec, conns, bodies, per) -> tuple[Recorder, float]:
    rec = Recorder(0, per)
    count = itertools.count()
    t0 = time.perf_counter()
    t_end = t0 + float(spec["seconds"])

    async def client(conn: Conn) -> Conn:
        while (now := time.perf_counter()) < t_end:
            i = rec.take()
            b = next(count) % len(bodies)
            rec.due[i] = now
            conn = await rec.one(i, conn, spec, bodies[b], b, per)
        return conn

    conns[:] = await asyncio.gather(*(client(c) for c in conns))
    return rec, t0


LOOPS = {"open": _open_loop, "closed": _closed_loop}


def outstanding_max(sent: np.ndarray, done: np.ndarray) -> int:
    """Most requests out at once (an answer frees its slot before a send
    at the same instant takes it)."""
    times = np.concatenate([done, sent])
    steps = np.concatenate([-np.ones(len(done)), np.ones(len(sent))])
    order = np.lexsort((steps, times))
    return int(np.cumsum(steps[order]).max()) if len(times) else 0


async def main_async(spec: dict) -> dict:
    bodies, per = _bodies(spec)
    path = f"/v1/models/{spec['model']}:predict"
    n_conns = int(spec["connections"])
    conns = [await Conn(spec["host"], spec["port"], path, j).open() for j in range(n_conns)]
    for j, c in enumerate(conns):  # warm every connection and the server path
        status, _ = await c.post(bodies[j % len(bodies)])
        if status != 200:
            raise RuntimeError(f"warm-up request answered {status}")
    print("ready", flush=True)
    line = await asyncio.get_running_loop().run_in_executor(None, sys.stdin.readline)
    if line.strip() != "go":
        raise RuntimeError(f"expected 'go', got {line!r}")
    rec, t0 = await LOOPS[spec["loop"]](spec, conns, bodies, per)
    for c in conns:
        c.close()
    res = rec.arrays()
    np.savez(spec["out"], t0=t0, **res)
    late = (res["sent"] - res["due"]) * 1e3
    return {
        "requests": int(len(res["status"])),
        "ok": int((res["status"] == 200).sum()),
        "outstanding_max": outstanding_max(res["sent"], res["done"]),
        "late_p50_ms": float(np.percentile(late, 50)) if late.size else 0.0,
        "late_p99_ms": float(np.percentile(late, 99)) if late.size else 0.0,
        "late_max_ms": float(late.max()) if late.size else 0.0,
    }


def main(argv: list[str]) -> int:
    spec = json.loads(Path(argv[1]).read_text())
    summary = asyncio.run(main_async(spec))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
