"""The load generator's process: drives the server over HTTP and times
every request on the client's clock. It imports no JAX and never touches
the chip; it is handed only the generated requests.

    python3 driver.py    # spec as one JSON line on stdin

One thread and one ``selectors`` loop over non-blocking keep-alive
connections, so the generator's own lateness stays small and is measured
(a second thread only waits for the parent's ``GO``).
The conversation with the parent, on stdin and stdout:

1. the parent writes the spec (requests, schedule, loop) as one line;
2. the driver runs the warm phase and prints ``READY``: an open loop's
   warm phase is drained first, a closed loop runs on (below);
3. the parent writes ``GO``; the driver reads ``/statsz``, runs the
   window, reads ``/statsz`` again at its close and prints ``DONE``;
4. the driver waits for what is still in flight (at most ``drain_s``) and
   prints one JSON line: the window's bounds on the monotonic clock, both
   ``/statsz`` bodies, and per window request its due and send times
   (open loop), its finish time, HTTP status and response body.

Open loop: request ``i`` is due at the window's start plus its offset and
is sent then on an idle connection (pipelined on the least busy one when
none is idle); its latency runs from the due time. Closed loop: each of
``clients`` connections sends the next request of the pool the moment its
last one returns, from the warm phase's start until the window closes,
so the window opens on a loop in its steady state and not on a refill.
Its requests are those in flight at the window's start and those sent
after it.
"""
from __future__ import annotations

import collections
import gc
import json
import selectors
import socket
import sys
import threading
import time

STATSZ = b"GET /statsz HTTP/1.1\r\nHost: bench\r\n\r\n"


def encode(path: str, body: str) -> bytes:
    data = body.encode()
    return (b"POST %s HTTP/1.1\r\nHost: bench\r\n"
            b"Content-Type: application/json\r\nContent-Length: %d\r\n\r\n"
            % (path.encode(), len(data))) + data


class Conn:
    def __init__(self, host: str, port: int):
        self.sock = socket.create_connection((host, port))
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.setblocking(False)
        self.out = bytearray()
        self.inbuf = bytearray()
        self.inflight = collections.deque()
        self.dead = False


class Engine:
    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.sel = selectors.DefaultSelector()
        self.conns = []
        self.idle = []
        self.sent = {}
        self.finish = {}
        self.status = {}
        self.body = {}
        self.on_done = None

    def open(self, n: int) -> list:
        new = []
        for _ in range(n):
            c = Conn(self.host, self.port)
            self.sel.register(c.sock, selectors.EVENT_READ, c)
            self.conns.append(c)
            new.append(c)
        return new

    def inflight(self) -> int:
        return sum(len(c.inflight) for c in self.conns if not c.dead)

    def send(self, c: Conn, key, data: bytes) -> None:
        self.sent[key] = time.monotonic()
        c.inflight.append(key)
        if c.out:
            c.out += data
            return
        try:
            n = c.sock.send(data)
        except BlockingIOError:
            n = 0
        except OSError:
            self._kill(c)
            return
        if n < len(data):
            c.out += data[n:]
            self.sel.modify(c.sock, selectors.EVENT_READ |
                            selectors.EVENT_WRITE, c)

    def pick(self) -> Conn:
        """An idle pool connection, else the least busy one."""
        while self.idle:
            c = self.idle.pop()
            if not c.dead and not c.inflight:
                return c
        return min((c for c in self.conns if not c.dead),
                   key=lambda c: len(c.inflight))

    def _kill(self, c: Conn) -> None:
        if c.dead:
            return
        c.dead = True
        try:
            self.sel.unregister(c.sock)
        except (KeyError, ValueError):
            pass
        c.sock.close()
        c.inflight.clear()        # never answered: no finish, no status

    def poll(self, timeout: float) -> None:
        for key, mask in self.sel.select(max(timeout, 0.0)):
            c = key.data
            if mask & selectors.EVENT_WRITE and c.out:
                try:
                    n = c.sock.send(c.out)
                    del c.out[:n]
                except BlockingIOError:
                    pass
                except OSError:
                    self._kill(c)
                    continue
                if not c.out:
                    self.sel.modify(c.sock, selectors.EVENT_READ, c)
            if mask & selectors.EVENT_READ:
                try:
                    data = c.sock.recv(1 << 20)
                except BlockingIOError:
                    continue
                except OSError:
                    data = b""
                now = time.monotonic()
                if not data:
                    self._kill(c)
                    continue
                c.inbuf += data
                self._parse(c, now)

    def _parse(self, c: Conn, now: float) -> None:
        while True:
            end = c.inbuf.find(b"\r\n\r\n")
            if end < 0:
                return
            head = bytes(c.inbuf[:end]).decode("latin-1").split("\r\n")
            length = 0
            for h in head[1:]:
                k, _, v = h.partition(":")
                if k.strip().lower() == "content-length":
                    length = int(v)
            if len(c.inbuf) < end + 4 + length:
                return
            key = c.inflight.popleft()
            self.finish[key] = now
            self.status[key] = int(head[0].split()[1])
            self.body[key] = bytes(c.inbuf[end + 4:end + 4 + length])
            del c.inbuf[:end + 4 + length]
            if not c.inflight:
                self.idle.append(c)
            if self.on_done is not None:
                self.on_done(c, key, now)

    def run_until(self, t_end: float, schedule=(), drain: bool = False,
                  drain_until: float = 0.0) -> None:
        """Send ``schedule`` (``(due, key, bytes)`` sorted by due) on
        time until ``t_end``; with ``drain``, then wait for everything in
        flight until ``drain_until``."""
        i, n = 0, len(schedule)
        while True:
            now = time.monotonic()
            while i < n and schedule[i][0] <= now:
                _, key, data = schedule[i]
                self.send(self.pick(), key, data)
                i += 1
            if now >= t_end and i >= n:
                if not drain or not self.inflight() or now >= drain_until:
                    return
                self.poll(min(0.01, drain_until - now))
                continue
            nxt = schedule[i][0] if i < n else t_end
            self.poll(min(0.01, nxt - now))

    def fetch_statsz(self, c: Conn) -> dict:
        key = ("statsz", time.monotonic())
        self.send(c, key, STATSZ)
        while key not in self.finish and not c.dead:
            self.poll(0.01)
        return json.loads(self.body[key])["stats"] if key in self.body \
            else {}


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    reqs = [encode(path, body) for path, body in spec["requests"]]
    del spec["requests"]
    # no collector pauses while timing: the loop makes no cycles, and a
    # full collection over the request set stalls sending for tens of ms
    gc.collect()
    gc.freeze()
    gc.disable()
    eng = Engine(spec["host"], spec["port"])
    ctl = eng.open(1)[0]                  # /statsz only, never in the pool
    eng.conns.remove(ctl)
    drain_s = spec["drain_s"]
    closed = spec["loop"] == "closed"
    if closed:
        clients = eng.open(spec["clients"])
        nxt = [0]
        stop = [float("inf")]

        def on_done(c, key, now):
            if c is not ctl and now < stop[0] and nxt[0] < len(reqs):
                eng.send(c, nxt[0], reqs[nxt[0]])
                nxt[0] += 1
        eng.on_done = on_done
    else:
        eng.open(spec["connections"])
        eng.idle = list(eng.conns)
        warm_n = len(spec["warm_due"])

    # warm phase: the same traffic; an open loop's is drained, a closed
    # loop runs on into the window
    t = time.monotonic() + 0.05
    if closed:
        for c in clients:
            if nxt[0] < len(reqs):
                eng.send(c, nxt[0], reqs[nxt[0]])
                nxt[0] += 1
        eng.run_until(t + spec["warm_s"])
    else:
        sched = [(t + d, i, reqs[i]) for i, d in enumerate(spec["warm_due"])]
        eng.run_until(t + spec["warm_s"], sched, drain=True,
                      drain_until=t + spec["warm_s"] + drain_s)
    print("READY", flush=True)
    word = []
    reader = threading.Thread(target=lambda: word.append(
        sys.stdin.readline()), daemon=True)
    reader.start()
    while not word:                     # the loop serves on meanwhile
        eng.poll(0.01)
    if word[0].strip() != "GO":
        return 2
    before = eng.fetch_statsz(ctl)

    w0 = time.monotonic()
    w1 = w0 + spec["seconds"]
    if closed:
        stop[0] = w1
        sent_before = nxt[0]
        carried = {k for c in eng.conns if not c.dead for k in c.inflight}
        eng.run_until(w1)
        keys = sorted(carried) + list(range(sent_before, nxt[0]))
        due = {}
    else:
        due = {warm_n + j: w0 + d for j, d in enumerate(spec["window_due"])}
        sched = [(d, i, reqs[i]) for i, d in sorted(due.items(),
                                                    key=lambda kv: kv[1])]
        eng.run_until(w1, sched)
        keys = sorted(due)
    skey = ("statsz", w1)
    eng.send(ctl, skey, STATSZ)
    print("DONE", flush=True)
    eng.run_until(w1, drain=True, drain_until=w1 + drain_s)
    while skey not in eng.body and not ctl.dead \
            and time.monotonic() < w1 + drain_s:
        eng.poll(0.01)
    after = json.loads(eng.body[skey])["stats"] if skey in eng.body else {}
    for c in eng.conns + [ctl]:
        if not c.dead:
            c.sock.close()

    records = [[k, due.get(k), eng.sent.get(k), eng.finish.get(k),
                eng.status.get(k),
                eng.body[k].decode() if k in eng.body else None]
               for k in keys]
    print(json.dumps({"w0": w0, "w1": w1, "statsz_before": before,
                      "statsz_after": after, "records": records,
                      "exhausted": closed and nxt[0] >= len(reqs)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
