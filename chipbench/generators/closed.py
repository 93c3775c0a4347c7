"""Closed loop: ``clients`` requests always outstanding from one tenant;
each completion sends the next.

A traffic file with ``"generator": "closed"`` gives ``clients`` and the
``buckets`` of one ``VTAServeEngine`` over the cell's model, otherwise at
its defaults (no holdback). The window closes at the first
completion at or after ``seconds``, so it always ends on a whole batch and
a rate over it carries no part-batch error.
"""
from __future__ import annotations

from collections import deque

LATE_WAIT_S = 60.0      # a request not done this long after the close never comes


def make_engine(models: dict, executor, traffic: dict):
    """The engine the window drives, not yet started."""
    from repro.serve.engine import VTAServeEngine
    return VTAServeEngine(models, executor=executor,
                          buckets=tuple(traffic["buckets"]))


def _wait(ticket, until: float, clock) -> None:
    try:
        ticket.result(max(0.0, until - clock.now()))
    except (TimeoutError, RuntimeError):
        pass                        # judged from the ticket's status below


def drive(engine, model_key: str, images, traffic: dict, seconds: float,
          seed: int, on_open=None, on_close=None, on_tick=None) -> dict:
    """Run the window on a started ``engine``. Returns ``t0``, ``t_close``,
    ``requests`` (one dict per request sent: image index, submit and done
    times, done None for one that failed or never came, status, output) and
    ``counted``, the requests the end-to-end metrics count: those completed
    by the close. ``on_open`` is called just before the first request is
    sent, ``on_close`` on the closing completion, and ``on_tick(now)`` after
    each other completion, on the same thread. Every seed offers the same
    work: the images differ, the sizes and the loop do not."""
    clock = engine.clock
    sent = []                       # (image index, submit time, ticket)

    def send(i: int):
        t = engine.submit("client", model_key, images[i % len(images)])
        sent.append((i % len(images), clock.now(), t))
        return t

    if on_open is not None:
        on_open()
    t0 = clock.now()
    close_at = t0 + seconds
    outstanding = deque(send(i) for i in range(int(traffic["clients"])))
    n = len(outstanding)
    while outstanding:
        head = outstanding.popleft()
        _wait(head, close_at + LATE_WAIT_S, clock)
        r = head.request
        if r.status == "done" and r.done_t >= close_at:
            break
        if not head.done():
            break                          # never came: stop the loop
        if on_tick is not None:
            on_tick(clock.now())
        outstanding.append(send(n))
        n += 1
    if on_close is not None:
        on_close()
    deadline = max(close_at, clock.now()) + LATE_WAIT_S
    for *_, t in sent:
        _wait(t, deadline, clock)
    reqs = [{"image": i, "submit": sub,
             "done": t.request.done_t if t.request.status == "done" else None,
             "status": t.request.status if t.done() else "never",
             "output": t.request.result if t.request.status == "done"
             else None}
            for i, sub, t in sent]
    late = [r["done"] for r in reqs
            if r["done"] is not None and r["done"] >= close_at]
    t_close = min(late) if late else clock.now()
    counted = [r for r in reqs if r["done"] is not None and r["done"] <= t_close]
    return {"t0": t0, "t_close": t_close, "requests": reqs, "counted": counted}
