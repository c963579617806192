"""The one traffic generator.  A mix is a data file of parameters
(``benchmarks/traffic/<name>.json``); two loops read them:

``closed``  ``clients`` threads, each sending its next request when the
            last one returned.  The query list is dealt round-robin to the
            clients and sent in order.
``paced``   request *i* is due at ``i / rate`` seconds (no Poisson, no
            bursts); ``senders`` threads take the next due request, sleep
            until it is due and send it, so that a slow reply delays no
            later request while a sender is free.  Latency counts from the
            due time (the arithmetic of ``opensearch_tpu/testing/
            loadgen.py``), and how late a request left is kept beside it.

Inside a loop nothing runs but sending, receiving and appending one
record: responses are kept and compared after the window.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time


@dataclasses.dataclass
class Record:
    qi: int                  # index into the query list
    due: float               # monotonic seconds; the send time when closed
    sent: float
    done: float
    resp: object             # the parsed response, or the exception


def _send(send, qi: int, due: float, out: list) -> None:
    sent = time.monotonic()
    try:
        resp = send(qi)
    except Exception as exc:      # the failure is the record's answer
        resp = exc
    out.append(Record(qi, due, sent, time.monotonic(), resp))


def run_closed(send, order: list, clients: int, seconds: float) -> tuple:
    """``order``: the query indices to send; client c takes every
    ``clients``-th from c and wraps.  Returns (records, t_start, t_end)."""
    outs = [[] for _ in range(clients)]
    start = threading.Barrier(clients + 1)
    t_end = [0.0]

    def client(c: int) -> None:
        mine = order[c::clients] or order
        start.wait()
        for qi in itertools.cycle(mine):
            now = time.monotonic()
            if now >= t_end[0]:
                return
            _send(send, qi, now, outs[c])

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    t_start = time.monotonic()
    t_end[0] = t_start + seconds
    start.wait()
    for t in threads:
        t.join()
    return [r for out in outs for r in out], t_start, t_end[0]


def run_paced(send, order: list, rate: float, senders: int,
              seconds: float) -> tuple:
    """Every request due in ``seconds`` is sent and waited for."""
    n = int(rate * seconds)
    outs = [[] for _ in range(senders)]
    ticket = itertools.count()
    lock = threading.Lock()
    t_start = time.monotonic() + 0.05

    def sender(s: int) -> None:
        while True:
            with lock:
                i = next(ticket)
            if i >= n:
                return
            due = t_start + i / rate
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            _send(send, order[i % len(order)], due, outs[s])

    threads = [threading.Thread(target=sender, args=(s,), daemon=True)
               for s in range(senders)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [r for out in outs for r in out], t_start, t_start + seconds


def run(params: dict, send, order: list, seconds: float) -> tuple:
    if params["loop"] == "closed":
        return run_closed(send, order, int(params["clients"]), seconds)
    if params["loop"] == "paced":
        return run_paced(send, order, float(params["rate"]),
                         int(params["senders"]), seconds)
    raise ValueError(f"unknown loop [{params['loop']}]")
