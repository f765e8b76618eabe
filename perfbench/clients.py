"""Closed-loop clients driving ``ServingEngine.run()`` through a whole window.

The engine drains its queue and returns; it polls the queue in its admission
step after every model step.  ``Inbox`` is the engine's queue: a deque that,
each time the engine asks whether work is waiting, first lets the clients
send what has come due.  So one ``run()`` stays alive for the whole window
and a request is admitted at the first admission after it is due.  A
client's next request is due when its previous request's last token was
stamped (no think time); the engine's ``submit`` stamps the request, and the
loop sets the stamp back to the due time, so the wait until submission
counts against the system, and the gap is reported as injection delay.

Phases: a ramp, until every client has finished a request; the window of
``seconds``; then no new request is sent and the engine drains what is in
flight.  A traced run starts the profiler at the window's start and stops it
``trace_seconds`` later, between two engine steps, where the device is idle
(the engine waits for each step's tokens before it admits).
"""
from __future__ import annotations

import collections
import time
from typing import Callable, List, Optional

from repro.serving import Request


class Inbox(collections.deque):
    """The engine's queue, polling the clients before every emptiness test."""

    def __init__(self, poll: Callable[[], None]):
        super().__init__()
        self._poll = poll

    def __bool__(self):
        self._poll()
        return len(self) > 0

    def popleft(self):
        """The engine takes the head request to admit it; ``taken`` is it."""
        self.taken = super().popleft()
        return self.taken


class ClosedLoop:
    def __init__(self, engine, streams, seconds: float, *,
                 on_window: Optional[Callable[[str], None]] = None,
                 on_send: Optional[Callable[[Request, int], None]] = None,
                 trace_seconds: float = 0.0):
        self.engine = engine
        self.streams = streams
        self.seconds = seconds
        self.on_window = on_window
        self.on_send = on_send
        self.trace_seconds = trace_seconds
        self.inflight: List[Optional[Request]] = [None] * len(streams)
        self.first_done = [False] * len(streams)
        self.sent: List[Request] = []
        self.due = {}                    # uid -> due time
        self.delays: List[float] = []    # submission - due, seconds
        self.window: Optional[tuple] = None
        self.trace_window: Optional[list] = None
        self._uid = 0
        engine.queue = Inbox(self.poll)

    def _send(self, client: int, due: float, previous) -> None:
        prompt, new = self.streams[client].next(previous)
        r = Request(uid=self._uid, prompt=prompt, max_new_tokens=new)
        self._uid += 1
        if self.on_send is not None:
            self.on_send(r, client)
        self.engine.submit(r)
        self.delays.append(r.submit_time - due)
        r.submit_time = due
        self.due[r.uid] = due
        self.inflight[client] = r
        self.sent.append(r)

    def start(self) -> None:
        now = time.perf_counter()
        for c in range(len(self.streams)):
            self._send(c, now, None)

    def poll(self) -> None:
        now = time.perf_counter()
        if self.trace_window and len(self.trace_window) == 1 \
                and now >= self.trace_window[0] + self.trace_seconds:
            self.on_window("trace_stop")
            self.trace_window.append(time.perf_counter())
        for c, r in enumerate(self.inflight):
            if r is None or not r.done:
                continue
            self.inflight[c] = None
            self.first_done[c] = True
            due = r.token_times[-1] if r.token_times else now
            if self.window is None and all(self.first_done):
                self.window = (now, now + self.seconds)
                if self.on_window is not None:
                    self.on_window("window_start")
                if self.trace_seconds:
                    self.on_window("trace_start")
                    self.trace_window = [time.perf_counter()]
            if self.window is not None and due >= self.window[1]:
                continue  # the window has closed: this client stops
            self._send(c, due, (r.prompt, r.output))

    def run(self) -> int:
        """Drive the engine until the window has closed and every request
        sent has retired; returns how often ``run()`` was re-entered."""
        self.start()
        entries = 0
        while True:
            self.engine.run()
            entries += 1
            self.poll()  # retire the last step's requests, send what is due
            if not len(self.engine.queue) and not any(self.inflight):
                break
        if self.trace_window and len(self.trace_window) == 1:
            self.on_window("trace_stop")
            self.trace_window.append(time.perf_counter())
        return entries - 1
