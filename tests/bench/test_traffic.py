"""The closed loop against a stand-in server: every client's reply brings
its next request, and the window closes on a whole number of passes over
the pool."""
import types

import numpy as np
import pytest

from bench import traffic


class _Server:
    def __init__(self, max_batch):
        self.max_batch, self.queue, self.t = max_batch, [], 0.0

    def submit(self, req):
        self.queue.append(req)

    def pending(self):
        return len(self.queue)

    def step(self):
        self.t += 1.0
        out, self.queue = (self.queue[:self.max_batch],
                           self.queue[self.max_batch:])
        return [types.SimpleNamespace(req_id=r.req_id, op="range",
                                      ids=[r.req_id]) for r in out]


def _request(req_id, query, radius):
    return types.SimpleNamespace(req_id=req_id, query=query, radius=radius)


@pytest.mark.parametrize("seconds,passes", [(1.0, 1), (5.0, 2), (8.0, 2),
                                            (8.5, 3)])
def test_window_closes_on_whole_passes(seconds, passes):
    srv = _Server(max_batch=4)
    queries = np.zeros((16, 2), np.float32)
    log, t0, t1 = traffic.run_closed(
        srv, _request, queries, 0.1, {"clients": 8}, seconds,
        clock=lambda: srv.t)
    assert t1 - t0 >= seconds
    done = [rid for rid, t in log.done.items() if t <= t1]
    assert len(done) == passes * len(queries)
    counts = np.bincount([log.pool_idx[r] for r in done])
    assert (counts == passes).all()
    # the clients still waiting when the window closes are drained after
    assert srv.pending() == 8 - 4
    traffic.drain(srv, log, clock=lambda: srv.t)
    assert len(log.done) == len(log.pool_idx)
