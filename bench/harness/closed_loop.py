"""A closed loop of clients in front of the front door.

Each client thread takes the next query of the shared stream, submits
it, waits for the answer in its own hands and, after the mix's think
time, sends the next one, until the window closes.  The clients start
a pre-roll before the window opens, so that the window finds the loop
in its steady state rather than every client's first request at once.
A request still out when the window closes is waited for up to
``SETTLE_S`` more seconds: an answer that comes late is late, and its
latency counts the wait.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, List, Optional

#: how long after the window closes an outstanding answer is awaited
SETTLE_S = 60.0


@dataclasses.dataclass
class Request:
    """One request of the window, as its client saw it."""
    client: int
    shape: int
    edges: list
    submitted: float                  # perf_counter seconds
    done: Optional[float] = None      # when the answer (or error) arrived
    result: Any = None                # the program's QueryResult
    error: Optional[str] = None       # why it failed, if it did

    @property
    def latency(self) -> Optional[float]:
        return None if self.done is None else self.done - self.submitted


def run(door, stream, make_query, clients: int, think_s: float,
        preroll_s: float, seconds: float, on_start=None, on_end=None
        ) -> tuple:
    """Drive ``clients`` closed-loop clients for ``preroll_s`` and then
    a window of ``seconds``.

    ``make_query(edges)`` builds the program's query object;
    ``on_start()`` runs just before the window opens and ``on_end()`` as
    it closes, both on the calling thread.  Returns (window start,
    window end, every request: those submitted in the pre-roll first),
    the times on ``time.perf_counter``'s clock.
    """
    release = threading.Event()
    requests: List[Request] = []
    lock = threading.Lock()
    bounds = {}

    def client(cid: int) -> None:
        release.wait()
        while True:
            shape, edges = stream.next()
            query = make_query(edges)
            t0 = time.perf_counter()
            w1 = bounds["end"]
            if t0 >= w1:
                return
            req = Request(cid, shape, edges, t0)
            with lock:
                requests.append(req)
            try:
                fut = door.submit(query)
                req.result = fut.result(timeout=max(
                    w1 + SETTLE_S - time.perf_counter(), 0.0))
            except TimeoutError:
                req.error = "no answer within the settle time"
                return
            except Exception as exc:          # a shed or failed request
                req.error = f"{type(exc).__name__}: {exc}"
            req.done = time.perf_counter()
            if think_s:
                time.sleep(think_s)

    threads = [threading.Thread(target=client, args=(c,),
                                name=f"bench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    bounds["end"] = time.perf_counter() + preroll_s + seconds
    release.set()
    time.sleep(preroll_s)
    if on_start is not None:
        on_start()
    w0 = time.perf_counter()
    bounds["end"] = w0 + seconds
    time.sleep(max(bounds["end"] - time.perf_counter(), 0.0))
    if on_end is not None:
        on_end()
    for t in threads:
        t.join(timeout=preroll_s + seconds + SETTLE_S + 30.0)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a client thread did not finish")
    return w0, bounds["end"], requests
