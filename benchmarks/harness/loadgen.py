"""Offers a :class:`~traffic.Plan` to the served ``LM.Decode`` and
stamps, on one monotonic clock, when each request was due, when it was
sent, when each of its tokens was received and when its stream closed.

One dispatcher thread sends everything; the channel's own thread runs
the stream callbacks, which only stamp and queue.  A closed loop keeps
``clients`` sessions going: a session's next turn, or a caller's next
session, is sent when the last stream closed.  An open loop starts a
session at every arrival, whatever the system does; its later turns
follow as in a closed loop.
"""

from __future__ import annotations

import queue
import threading
import time

now = time.monotonic
START_STAGGER_S = 0.1       # between a closed loop's first sessions
PASS_CONCURRENCY = 2        # sessions in flight in set-up's pass


class LoadGen:
    def __init__(self, served, plan, truncate_to: int = 0):
        self.served, self.plan = served, plan
        self.truncate_to = truncate_to      # set-up's pass: ask 1 token
        self.requests: list = []            # every request ever sent
        self.closed_sessions = 0
        self.closed_requests = 0
        self._events: queue.Queue = queue.Queue()
        self._stop = threading.Event()
        self._thread = None
        self._sessions = None               # a fixed list to play, or None
        self.error = None

    # -- sending ----------------------------------------------------------

    def _send(self, req, due: float, released: float) -> None:
        from brpc_tpu.client import Controller
        from brpc_tpu.models.lm_service import (pack_generate_request,
                                                unpack_token)
        from brpc_tpu.streaming import StreamOptions, stream_create

        if self.truncate_to:
            req.max_new = min(req.max_new, self.truncate_to)
        req.due, req.released = due, released
        self.requests.append(req)

        def on_received(_st, msgs, req=req):
            t = now()
            req.tokens.extend(unpack_token(m) for m in msgs)
            req.stamps.extend([t] * len(msgs))

        def on_closed(st, req=req):
            req.closed, req.reason = now(), st.close_reason
            self._events.put(req)

        cntl = Controller()
        cntl.timeout_ms = 60_000
        stream_create(cntl, StreamOptions(on_received=on_received,
                                          on_closed=on_closed))
        req.sent = now()
        c = self.served.channel.call_method(
            "LM.Decode", pack_generate_request(req.prompt[None], req.max_new),
            cntl=cntl)
        if c.failed:
            req.error = c.error_text
            req.closed, req.reason = now(), "refused"
            self._events.put(req)

    def _start_session(self, due: float, released: float, live: dict):
        if self._sessions is not None:
            if not self._sessions:
                return False
            reqs = self._sessions.pop(0)
        else:
            reqs = self.plan.next_session()
        live[reqs[0].session] = reqs[1:]
        self._send(reqs[0], due, released)
        return True

    def _run(self) -> None:
        try:
            self._loop()
        except Exception as e:             # surfaced by stop()/play()
            self.error = e

    def _loop(self) -> None:
        live: dict = {}                     # session id -> turns left
        passing = self._sessions is not None
        open_loop = self.plan.loop == "open" and not passing
        t = now()
        if open_loop:
            starts, next_due = [], t
        else:
            # callers arrive one after another, not all in one instant:
            # admissions that coincide are the harness's doing, not the
            # mix's, and each holds a whole prefilled cache in flight
            n = PASS_CONCURRENCY if passing \
                else int(self.plan.mix.get("clients", 1))
            starts = [t + i * START_STAGGER_S for i in range(n)]
            next_due = starts.pop(0)
        while not self._stop.is_set():
            if next_due is not None:
                wait = next_due - now()
                if wait <= 0:
                    self._start_session(next_due, next_due, live)
                    if starts:
                        next_due = starts.pop(0)
                    elif open_loop:
                        next_due += self.plan.next_gap()
                    else:
                        next_due = None
                    continue
            else:
                wait = 0.25
            try:
                req = self._events.get(timeout=min(wait, 0.25))
            except queue.Empty:
                continue
            self.closed_requests += 1
            rest = live.get(req.session)
            if rest:
                nxt = rest.pop(0)
                self._send(nxt, req.closed, req.closed)
                continue
            live.pop(req.session, None)
            self.closed_sessions += 1
            if not open_loop:
                more = self._start_session(req.closed, req.closed, live)
                if not more and not live and next_due is None:
                    return

    # -- control ----------------------------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="loadgen")
        self._thread.start()

    def stop(self) -> None:
        """Send nothing more.  Streams already open run on."""
        self._stop.set()
        self._thread.join(timeout=30)
        if self.error is not None:
            raise self.error

    def play(self, sessions: list, timeout_s: float) -> None:
        """Send exactly ``sessions`` (``PASS_CONCURRENCY`` at a time),
        wait for every stream to close."""
        self._sessions = list(sessions)
        self.start()
        self._thread.join(timeout=timeout_s)
        if self.error is not None:
            raise self.error
        if self._thread.is_alive():
            self._stop.set()
            raise RuntimeError("set-up's pass over the strata did not "
                               f"finish in {timeout_s:.0f}s")

    def drain(self, timeout_s: float) -> int:
        """Wait for every sent request's stream to close; returns how
        many never did."""
        deadline = now() + timeout_s
        while now() < deadline:
            if all(r.closed is not None for r in self.requests):
                return 0
            time.sleep(0.01)
        return sum(1 for r in self.requests if r.closed is None)
