"""HTTP load generator for the serving cells, run as its own process.

    python bench/loadgen.py SPEC.json

It never imports JAX, so the chip stays with the server's process.  It
makes its images from the run's seed (`bench.inputs`, numpy only),
opens its keep-alive connections, sends one warm-up request on each,
prints ``ready`` and waits for ``go`` on standard input.  Then it runs
an open loop, writes every request's times, status and labels to
``spec["out"]`` (``.npz``), and prints one JSON summary line.

Requests are due on the schedule drawn from the seed
(`inputs.schedule`), whatever the server does.  Each request's latency
runs from when it was due, not from when it was sent, so a stall is
charged to every request due during it; how late the generator sent is
reported beside it.

The protocol is the server's raw binary hot path: a POST of C-order
little-endian float32 rows (``application/x-hdc-f32``) answered by
int32 labels (``application/x-hdc-i32``).
"""

from __future__ import annotations

import asyncio
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

    def __init__(self, host: str, port: int, path: str):
        self.host, self.port, self.path = host, port, path
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
    def __init__(self, n: int, per: int):
        self.due = np.full(n, np.nan)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.status = np.zeros(n, np.int32)
        self.block = np.zeros(n, np.int32)
        self.labels = np.full((n, per), -1, np.int32)

    async def one(self, i: int, conn: Conn, spec: dict, body: bytes, block: int,
                  per: int) -> Conn:
        """Send request i on `conn`; returns a usable connection."""
        self.block[i] = block
        self.sent[i] = time.perf_counter()
        try:
            status, payload = await asyncio.wait_for(conn.post(body), spec["timeout_s"])
        except (OSError, asyncio.TimeoutError, asyncio.IncompleteReadError, ValueError):
            self.done[i] = time.perf_counter()
            self.status[i] = -1
            conn.close()
            return await Conn(conn.host, conn.port, conn.path).open()
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


async def main_async(spec: dict) -> dict:
    bodies, per = _bodies(spec)
    path = f"/v1/models/{spec['model']}:predict"
    n_conns = int(spec["connections"])
    conns = [await Conn(spec["host"], spec["port"], path).open() for _ in range(n_conns)]
    for j, c in enumerate(conns):  # warm every connection and the server path
        status, _ = await c.post(bodies[j % len(bodies)])
        if status != 200:
            raise RuntimeError(f"warm-up request answered {status}")
    print("ready", flush=True)
    line = await asyncio.get_running_loop().run_in_executor(None, sys.stdin.readline)
    if line.strip() != "go":
        raise RuntimeError(f"expected 'go', got {line!r}")
    rec, t0 = await _open_loop(spec, conns, bodies, per)
    for c in conns:
        c.close()
    np.savez(spec["out"], due=rec.due, sent=rec.sent, done=rec.done,
             status=rec.status, block=rec.block, labels=rec.labels, t0=t0)
    late = (rec.sent - rec.due) * 1e3
    return {
        "requests": int(len(rec.status)),
        "ok": int((rec.status == 200).sum()),
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
