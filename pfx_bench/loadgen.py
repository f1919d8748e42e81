"""The one general traffic generator and open-loop HTTP client (stdlib).

A traffic mix is a data file (traffic/<mix>.json).  ``build_plan`` turns it
and a seed into a request plan; ``OpenLoop`` replays the plan against
``POST /generate?stream=1`` from ONE thread (``selectors``, non-blocking
sockets), stamping every SSE token frame as it arrives.

Every seed gets the SAME multiset of prompt lengths, output lengths and
inter-arrival gaps, in ANOTHER order, and other token ids.  The sizes are
stratified quantiles of the mix's distributions.  The gaps are stratified
quantiles of the exponential distribution, scaled so that ``rate x seconds``
requests fill their phase exactly: the gaps' shape is a Poisson process's,
the count has no variance (it is not a Poisson process: the name would
overstate it).  What the generator can express today is one class of
independent requests at one mean rate; bursts, sessions that share a
prefix, mixed classes and load past the knee need additions here, which
only a ``benchmark`` PR may make (README.md).  (The arrival arithmetic
is a copy of the idea in benchmarks/bench_decode.py: seeded exponential
gaps replayed on a schedule.)"""

import errno
import json
import math
import random
import selectors
import socket
import statistics
import time

_NORMAL = statistics.NormalDist()


def _quantile(dist: dict, u: float) -> float:
    kind, lo, hi = dist["dist"], float(dist["min"]), float(dist["max"])
    if kind == "uniform":
        x = lo + u * (hi - lo)
    elif kind == "loguniform":
        x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif kind == "lognormal":
        x = float(dist["median"]) * math.exp(float(dist["sigma"]) * _NORMAL.inv_cdf(u))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return min(hi, max(lo, x))


def _stratified(dist: dict, n: int, rng: random.Random) -> list:
    vals = [int(round(_quantile(dist, (i + 0.5) / n))) for i in range(n)]
    rng.shuffle(vals)
    return vals


def _arrivals(n: int, start: float, duration: float, rng) -> list:
    """n due times in [start, start + duration): stratified exponential
    gaps, shuffled, scaled to fill the phase."""
    if n <= 0:
        return []
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = duration / sum(gaps)
    gaps = [g * scale for g in gaps]
    rng.shuffle(gaps)
    t, due = start + 0.5 * min(gaps), []
    for g in gaps:
        due.append(t)
        t += g
    return due


def build_plan(traffic: dict, seed: int, seconds: float, vocab: int,
               rate: float = None) -> dict:
    """-> {"lead_in_s", "seconds", "rate_rps", "requests": [{"due", "phase",
    "prompt_ids", "max_tokens"}, ...]} with ``due`` relative to the start of
    the lead-in.  ``rate`` overrides the mix's own (the knee sweep)."""
    rate = float(traffic["rate_rps"] if rate is None else rate)
    lead = float(traffic["lead_in_s"])
    rng = random.Random(f"order:{seed}")
    ids = random.Random(f"ids:{seed}")
    reqs = []
    for phase, start, dur in (("lead", 0.0, lead), ("window", lead, float(seconds))):
        n = int(round(rate * dur))
        due = _arrivals(n, start, dur, rng)
        plens = _stratified(traffic["prompt_len"], n, rng)
        outs = _stratified(traffic["max_tokens"], n, rng)
        for d, p, o in zip(due, plens, outs):
            reqs.append({"due": d, "phase": phase, "max_tokens": max(1, o),
                         "prompt_ids": [ids.randrange(1, vocab) for _ in range(max(1, p))]})
    reqs.sort(key=lambda r: r["due"])
    return {"lead_in_s": lead, "seconds": float(seconds), "rate_rps": rate,
            "requests": reqs}


def prompt_buckets(traffic: dict, multiple: int) -> list:
    """The prompt-length buckets (multiples of the server's
    ``pad_to_multiple``) this mix can land on: what the server warms."""
    lo, hi = int(traffic["prompt_len"]["min"]), int(traffic["prompt_len"]["max"])
    first = -(-max(1, lo) // multiple) * multiple
    last = -(-hi // multiple) * multiple
    return list(range(first, last + 1, multiple))


# ===========================================================================
# Open-loop client
# ===========================================================================


class _Conn:
    __slots__ = ("idx", "sock", "out", "buf", "status", "head_done", "frames",
                 "tokens", "error", "done", "sent_at", "summary")

    def __init__(self, idx, sock, out):
        self.idx, self.sock, self.out = idx, sock, out
        self.buf = b""
        self.status = None
        self.head_done = False
        self.frames = []   # (monotonic time, tokens in the frame)
        self.tokens = {}   # index -> token id
        self.error = None
        self.done = False
        self.sent_at = None
        self.summary = False


class OpenLoop:
    """Replay a plan on its schedule; ``results()`` gives one record per
    request.  ``t0`` (monotonic) is the start of the lead-in."""

    def __init__(self, port: int, plan: dict, deadline_s: float):
        self.port, self.plan, self.deadline_s = port, plan, deadline_s
        self.sel = selectors.DefaultSelector()
        self.conns = {}
        self.t0 = None

    def _open(self, idx: int, now: float) -> None:
        req = self.plan["requests"][idx]
        body = json.dumps({"prompt_ids": req["prompt_ids"],
                           "max_tokens": req["max_tokens"],
                           "deadline_s": self.deadline_s}).encode()
        head = (f"POST /generate?stream=1 HTTP/1.1\r\nHost: 127.0.0.1:{self.port}\r\n"
                "Content-Type: application/json\r\nAccept: text/event-stream\r\n"
                f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n").encode()
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setblocking(False)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        c = _Conn(idx, s, head + body)
        self.conns[idx] = c
        rc = s.connect_ex(("127.0.0.1", self.port))
        if rc not in (0, errno.EINPROGRESS):
            c.error, c.done = f"connect: {errno.errorcode.get(rc, rc)}", True
            s.close()
            return
        self.sel.register(s, selectors.EVENT_WRITE, c)

    def _close(self, c: _Conn, error=None) -> None:
        if error and not c.error:
            c.error = error
        c.done = True
        try:
            self.sel.unregister(c.sock)
        except (KeyError, ValueError):
            pass
        c.sock.close()

    def _on_write(self, c: _Conn, now: float) -> None:
        err = c.sock.getsockopt(socket.SOL_SOCKET, socket.SO_ERROR)
        if err:
            return self._close(c, f"connect: {errno.errorcode.get(err, err)}")
        try:
            n = c.sock.send(c.out)
        except (BlockingIOError, InterruptedError):
            return
        except OSError as e:
            return self._close(c, f"send: {e}")
        if c.sent_at is None:
            c.sent_at = now
        c.out = c.out[n:]
        if not c.out:
            self.sel.modify(c.sock, selectors.EVENT_READ, c)

    def _on_read(self, c: _Conn, now: float) -> None:
        try:
            data = c.sock.recv(65536)
        except (BlockingIOError, InterruptedError):
            return
        except OSError as e:
            return self._close(c, f"recv: {e}")
        if not data:
            if c.status == 200 and not c.summary and not c.error:
                c.error = "stream closed without a summary frame"
            elif c.status is None:
                c.error = "closed before a status line"
            elif c.status != 200 and not c.error:
                c.error = f"HTTP {c.status}: {c.buf[:200].decode(errors='replace')}"
            return self._close(c)
        c.buf += data
        if not c.head_done:
            end = c.buf.find(b"\r\n\r\n")
            if end < 0:
                return
            try:
                c.status = int(c.buf.split(b" ", 2)[1])
            except (IndexError, ValueError):
                return self._close(c, "bad status line")
            c.buf = c.buf[end + 4:]
            c.head_done = True
        if c.status != 200:
            return
        while True:
            end = c.buf.find(b"\n\n")
            if end < 0:
                return
            frame, c.buf = c.buf[:end], c.buf[end + 2:]
            event, data = None, None
            for line in frame.split(b"\n"):
                if line.startswith(b"event: "):
                    event = line[7:].decode()
                elif line.startswith(b"data: "):
                    data = json.loads(line[6:])
            if event == "token" and data is not None:
                toks = data["tokens"]
                c.frames.append((now, len(toks)))
                for i, t in enumerate(toks):
                    c.tokens[data["index"] + i] = t
            elif event == "error":
                c.error = f"error frame: {data}"
            elif event == "summary":
                c.summary = True

    def run(self, t0: float, stop_sending_at: float, give_up_at: float) -> None:
        """Send each request when it is due (never after
        ``stop_sending_at``), read until all are done or ``give_up_at``
        (both monotonic)."""
        self.t0 = t0
        reqs = self.plan["requests"]
        nxt = 0
        while True:
            now = time.monotonic()
            while nxt < len(reqs) and t0 + reqs[nxt]["due"] <= now:
                if now <= stop_sending_at + 1.0:
                    self._open(nxt, now)
                nxt += 1
            live = [c for c in self.conns.values() if not c.done]
            if nxt >= len(reqs) and not live:
                break
            if now >= give_up_at:
                for c in live:
                    self._close(c, "not drained")
                break
            wait = 0.05
            if nxt < len(reqs):
                wait = min(wait, max(0.0, t0 + reqs[nxt]["due"] - now))
            if not self.sel.get_map():
                time.sleep(wait)
                continue
            for key, mask in self.sel.select(wait):
                c = key.data
                stamp = time.monotonic()
                if mask & selectors.EVENT_WRITE:
                    self._on_write(c, stamp)
                elif mask & selectors.EVENT_READ:
                    self._on_read(c, stamp)
        self.sel.close()

    def results(self) -> list:
        out = []
        for idx, req in enumerate(self.plan["requests"]):
            c = self.conns.get(idx)
            rec = {"idx": idx, "phase": req["phase"], "due": self.t0 + req["due"],
                   "prompt_len": len(req["prompt_ids"]), "max_tokens": req["max_tokens"]}
            if c is None:
                rec.update(sent=False, error="never sent", frames=[], tokens=[])
            else:
                rec.update(sent=True, sent_at=c.sent_at, status=c.status, error=c.error,
                           frames=c.frames,
                           tokens=[c.tokens[i] for i in sorted(c.tokens)],
                           contiguous=sorted(c.tokens) == list(range(len(c.tokens))))
            out.append(rec)
        return out
