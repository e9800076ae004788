"""The admission load generator: a JAX-free child of the benchmark.

    python benchmark/loadgen.py <plan.json>

One thread, one asyncio loop, real HTTP/1.1 over persistent connections to
the served port.  It shares the host's cores with the server but not its
GIL.  Bodies and the schedule are made by the parent from the seed before
the window; this process only sends them.

Open loop: request i leaves at ``start + schedule[i][0]`` whether or not
earlier ones were answered: on an idle one of the ``connections`` kept
open, and on a new one when none is idle, so a slow server never holds a
departure back (an apiserver multiplexes its webhook calls and does not
queue behind 32 sockets).  Its latency runs FROM THE TIME IT WAS DUE, and
how late it left is reported beside it.  Closed loop: each connection sends
its next request on the reply.  A request not answered within ``timeout_s``
of the time it was due has failed, as the apiserver gives a webhook
``timeoutSeconds`` from the moment it calls; its connection is closed.

Times in the output are seconds on ``time.monotonic()``, which parent and
child share on one host.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import answers  # noqa: E402

HEAD = (b"POST /v1/admit HTTP/1.1\r\nHost: 127.0.0.1\r\n"
        b"Content-Type: application/json\r\nContent-Length: %d\r\n\r\n")
SPIN_S = 0.0015  # an epoll sleep wakes up to a millisecond late


class Connection:
    def __init__(self, port: int):
        self.port = port
        self.reader = self.writer = None

    async def open(self) -> None:
        self.reader, self.writer = await asyncio.open_connection(
            "127.0.0.1", self.port)

    def close(self) -> None:
        if self.writer is not None:
            self.writer.close()
        self.reader = self.writer = None

    async def post(self, body: bytes) -> tuple:
        """(HTTP status, payload) of one request on this connection."""
        if self.writer is None:
            await self.open()
        self.writer.write(HEAD % len(body) + body)
        await self.writer.drain()
        status = int((await self.reader.readline()).split()[1])
        length, close = 0, False
        while True:
            line = await self.reader.readline()
            if line in (b"\r\n", b""):
                break
            name, _, value = line.partition(b":")
            name = name.strip().lower()
            if name == b"content-length":
                length = int(value)
            elif name == b"connection" and value.strip().lower() == b"close":
                close = True
        payload = await self.reader.readexactly(length)
        if close:
            self.close()
        return status, payload


async def send(conn: Connection, body: bytes, deadline: float) -> tuple:
    """(done time or None, HTTP status or None, digest or None, code)."""
    try:
        status, payload = await asyncio.wait_for(
            conn.post(body), max(0.0, deadline - time.monotonic()))
    except (asyncio.TimeoutError, OSError, asyncio.IncompleteReadError,
            ValueError, IndexError):
        conn.close()  # an answer may still be on its way on this socket
        return None, None, None, None
    done = time.monotonic()
    if status != 200:
        return done, status, None, None
    dig, code = answers.of_response(json.loads(payload))
    return done, status, dig, code


async def sleep_until(t: float) -> None:
    wait = t - time.monotonic()
    if wait > SPIN_S:
        await asyncio.sleep(wait - SPIN_S)
    while time.monotonic() < t:
        await asyncio.sleep(0)


async def open_loop(plan: dict, bodies: list, conns: list) -> list:
    idle = list(conns)
    rows: list = []
    tasks: list = []

    async def one(conn, row) -> None:
        row[3:] = await send(conn, bodies[row[0]],
                             row[1] + plan["timeout_s"])
        idle.append(conn)

    for offset, index in plan["schedule"]:
        due = plan["start"] + offset
        await sleep_until(due)
        if not idle:
            conns.append(Connection(plan["port"]))
            idle.append(conns[-1])
        row = [index, due, time.monotonic(), None, None, None, None]
        rows.append(row)
        tasks.append(asyncio.create_task(one(idle.pop(), row)))
    await asyncio.gather(*tasks)
    return rows


async def closed_loop(plan: dict, bodies: list, conns: list) -> list:
    sequence = plan["sequence"]
    end = plan["start"] + plan["warmup_s"] + plan["seconds"]
    rows: list = []
    cursor = [0]

    async def worker(conn) -> None:
        while time.monotonic() < end:
            index = sequence[cursor[0] % len(sequence)]
            cursor[0] += 1
            sent = time.monotonic()
            row = [index, sent, sent,
                   *await send(conn, bodies[index],
                               sent + plan["timeout_s"])]
            rows.append(row)

    await sleep_until(plan["start"])
    await asyncio.gather(*(worker(c) for c in conns))
    return rows


async def run(plan: dict) -> dict:
    with open(plan["bodies"], "rb") as f:
        bodies = [line.rstrip(b"\n") for line in f]
    conns = [Connection(plan["port"]) for _ in range(plan["connections"])]
    await asyncio.gather(*(c.open() for c in conns))
    loop = open_loop if plan["loop"] == "open" else closed_loop
    rows = await loop(plan, bodies, conns)
    for c in conns:
        c.close()
    return {"columns": ["body", "due", "sent", "done", "http_status",
                        "digest", "code"], "rows": rows,
            "connections_peak": len(conns)}


def main(plan_path: str) -> int:
    with open(plan_path) as f:
        plan = json.load(f)
    out = asyncio.run(run(plan))
    if "jax" in sys.modules:
        raise RuntimeError("the load generator imported jax")
    with open(plan["output"], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
