"""The one traffic generator and the loops that drive the server.

A mix is a data file ``bench/traffic/<name>.json``. The one kind there is:

* ``{"loop": "closed", "clients": C}``: C clients, each sends its next query
  as soon as its reply lands, with no think time.

Queries cycle through the pool in order, every request at the cell's radius.
The loop records, for every request, its pool index, when it was due, when
its response came back and the ids it returned.
"""
from __future__ import annotations

import contextlib
import json
import os
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# a request not answered this long after the window closes never came
LATE_S = 60.0


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


class Log:
    """Per-request record of one window."""

    def __init__(self):
        self.pool_idx: dict[int, int] = {}
        self.due: dict[int, float] = {}
        self.done: dict[int, float] = {}
        self.ids: dict[int, np.ndarray] = {}
        self.errors = 0

    def sent(self, rid: int, qi: int, due: float) -> None:
        self.pool_idx[rid] = qi
        self.due[rid] = due

    def got(self, responses, now: float) -> None:
        for r in responses:
            self.done[r.req_id] = now
            if r.op == "error":
                self.errors += 1
            else:
                self.ids[r.req_id] = np.asarray(r.ids)


def _null_span(name):
    return contextlib.nullcontext()


def _request(Request, rid, queries, qi, radius):
    return Request(req_id=rid, query=queries[qi], radius=radius)


def run_closed(server, Request, queries, radius, mix, seconds, *,
               span=_null_span, clock=time.perf_counter):
    """Closed loop; returns ``(log, t0, t_close)``. The window closes at the
    end of the first step that returns at or after ``seconds`` with a whole
    number of passes over the pool answered, so that every window serves
    each pool query equally often (or at the first step past that count,
    should a server answer in steps that do not add up to a pass)."""
    log, pool = Log(), len(queries)
    rid = answered = 0
    target = None   # answered count at which the window closes
    t0 = clock()
    with span("bench.submit"):
        for _ in range(mix["clients"]):
            server.submit(_request(Request, rid, queries, rid % pool, radius))
            log.sent(rid, rid % pool, t0)
            rid += 1
    while True:
        with span("bench.step"):
            out = server.step()
        now = clock()
        log.got(out, now)
        answered += len(out)
        if now - t0 >= seconds and target is None:
            target = -(-answered // pool) * pool
        if (target is not None and answered >= target) or not out:
            t_close = now
            break
        with span("bench.submit"):
            for _ in out:
                server.submit(_request(Request, rid, queries, rid % pool,
                                       radius))
                log.sent(rid, rid % pool, clock())
                rid += 1
    return log, t0, t_close


def drain(server, log: Log, clock=time.perf_counter) -> None:
    """Serve what is still queued after the window; give up ``LATE_S`` after
    the close."""
    stop = clock() + LATE_S
    while server.pending() and clock() < stop:
        log.got(server.step(), clock())
