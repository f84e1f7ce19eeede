"""The measured window: open-loop reads from client threads and, where the
mix has updates, deltas from one publisher thread, all in this process.

Each read is timed on the client's side from when it was due: a worker
takes the next request in due order, sleeps until it is due, calls
``client.query`` and stamps the answer's arrival. Each update record is
timed from when it was due until the ``client.update`` call that publishes
it returns. Reads ask for ``min_version`` of the newest acknowledged
version wherever updates flow (read-your-writes), so an answer older than
an acknowledged publish fails as stale.

What a request sends, and what of its answer is kept for the comparison,
is the deployment's ``send``: the window's one call per request. It may
make several ``client.query`` calls (dependent rounds) and returns an
``Answer``, stamped when its last round returned.
"""
from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np

OK, SHED, STALE, FAILED, MISSING = 0, 1, 2, 3, 4


@dataclasses.dataclass
class Answer:
    """What ``send`` returns for one request."""
    done: float                   # time.monotonic() as the last round returned
    version: int                  # the version the answer is judged at
    kept: object                  # what the deployment's ``compare`` reads


class Window:
    def __init__(self, client, sched, data, deltas, upserts, send, *,
                 clients: int, sample: set, on_publish=None, acked: int = 1):
        self.client = client
        self.sched = sched
        self.data = data
        self.deltas = deltas
        self.upserts = upserts          # delta -> client.update argument
        # (client, sched, data, i, consistency, timeout, sampled) -> Answer
        self.send = send
        self.n_clients = clients
        self.sample = sample            # requests whose whole answer is kept
        self.on_publish = on_publish
        n = len(sched.read_due)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.outcome = np.full(n, MISSING, dtype=np.int8)
        self.version = np.zeros(n, dtype=np.int64)
        self.min_version = np.zeros(n, dtype=np.int64)
        self.kept: list = [None] * n    # Answer.kept of each answered i
        self.errors: list = []
        self.pub_start = np.full(len(deltas), np.nan)
        self.pub_end = np.full(len(deltas), np.nan)
        self.acked = acked
        self._next = 0
        self._lock = threading.Lock()

    # ------------------------------------------------------------------
    def _read(self, t0: float, give_up: float) -> None:
        from repro.api.types import Consistency, ConsistencyError
        from repro.serve.scheduler import ShedError
        sched, n = self.sched, len(self.sched.read_due)
        while True:
            with self._lock:
                i = self._next
                self._next += 1
            if i >= n:
                return
            delay = t0 + sched.read_due[i] - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            v_min = self.acked
            self.min_version[i] = v_min
            consistency = Consistency.min_version(v_min) if self.deltas \
                else None
            self.sent[i] = time.monotonic()
            try:
                ans = self.send(self.client, sched, self.data, i,
                                consistency,
                                max(give_up - time.monotonic(), 0.001),
                                i in self.sample)
            except ShedError:
                self.outcome[i] = SHED
                continue
            except ConsistencyError as e:
                self.outcome[i] = STALE
                self.errors.append(repr(e))
                continue
            except Exception as e:  # noqa: BLE001 - counted, reported
                self.outcome[i] = FAILED
                self.errors.append(repr(e))
                continue
            self.done[i] = ans.done
            self.outcome[i] = OK
            self.version[i] = ans.version
            self.kept[i] = ans.kept

    def _publish(self, t0: float) -> None:
        for k, d in enumerate(self.deltas):
            delay = t0 + d.due_s - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            ups = self.upserts(d)
            self.pub_start[k] = time.monotonic()
            try:
                self.client.update(d.version, upserts=ups)
            except Exception as e:  # noqa: BLE001 - counted, reported
                self.errors.append(f"publish v{d.version}: {e!r}")
                return
            self.pub_end[k] = time.monotonic()
            self.acked = d.version
            if self.on_publish is not None:
                self.on_publish(d.version)

    # ------------------------------------------------------------------
    def start(self, t0: float, seconds: float, grace_s: float = 60.0):
        """Start every thread; the window runs from ``t0`` for
        ``seconds``, and answers may arrive up to ``grace_s`` after it."""
        give_up = t0 + seconds + grace_s
        self._threads = [threading.Thread(target=self._read,
                                          args=(t0, give_up),
                                          name=f"bench-client-{c}",
                                          daemon=True)
                         for c in range(self.n_clients)]
        if self.deltas:
            self._threads.append(threading.Thread(
                target=self._publish, args=(t0,), name="bench-publisher",
                daemon=True))
        for th in self._threads:
            th.start()
        self._give_up = give_up

    def join(self) -> bool:
        """Wait for every thread, at most until the grace ends; True when
        all of them ended."""
        for th in self._threads:
            th.join(max(self._give_up - time.monotonic(), 0.0) + 5.0)
        return not any(th.is_alive() for th in self._threads)

    # ------------------------------------------------------------------
    def update_visible(self) -> np.ndarray:
        """Per update record, when the publish carrying it returned (nan
        where it never did)."""
        out = np.full(len(self.sched.update_due), np.nan)
        for k, d in enumerate(self.deltas):
            out[d.records] = self.pub_end[k]
        return out
