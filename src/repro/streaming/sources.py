"""Unbounded sources for the micro-batch streaming engine.

A source is anything with the four-method contract the driver loop
speaks (docs/streaming.md):

  * ``initial()`` — the offset before any event was consumed;
  * ``next_offset(offset, limit)`` — snapshot up to ``limit`` more
    source units past ``offset`` (events for the generator, objects for
    the tailer) and return the new offset. Pure bookkeeping: no event
    data moves yet;
  * ``read(start, end)`` — the rows between two offsets. REPLAYABLE:
    the same offset pair must return the same rows forever, because
    exactly-once recovery re-reads the batch a crashed driver was
    processing (the checkpoint stores offsets, never rows);
  * ``exhausted(offset)`` — True when the stream has ended at
    ``offset`` (finite generator drained, sealed prefix fully
    consumed). An unbounded source simply always returns False.

Offsets are opaque to the driver but must pickle (they land in
``_stream/`` checkpoints) and compare equal across process restarts.
"""

from __future__ import annotations

import random
import time

from repro.core.retry import TransientServiceError
from repro.sql.expr import Schema, scan_cast


def ride_faults(fn, *args):
    """Call a store operation the way a driver SDK would, riding out the
    service-wide chaos injector's transient 5xxs with capped backoff (the
    last attempt surfaces the error)."""
    for i in range(8):
        try:
            return fn(*args)
        except TransientServiceError:
            time.sleep(min(0.25, 0.002 * (2 ** i)))
    return fn(*args)


class EventGenerator:
    """Seeded in-memory event stream: rows ``(ts, key, val)`` with
    integer event time, bounded out-of-orderness, and fully deterministic
    replay — event ``i`` is a pure function of ``(seed, i)``, so
    ``read(start, end)`` returns identical rows no matter how batches
    were cut before a crash.

    ``rate`` events share each event-time tick; with probability
    ``late_prob`` an event's ts lags its arrival position by up to
    ``max_delay`` ticks (the watermark/late-data surface under test).
    ``total`` bounds the stream (None = unbounded)."""

    schema = Schema([("ts", "int"), ("key", "str"), ("val", "int")])

    def __init__(self, *, seed: int = 0, n_keys: int = 4, rate: int = 10,
                 late_prob: float = 0.2, max_delay: int = 5,
                 total: int | None = None):
        if rate <= 0 or n_keys <= 0 or max_delay < 0:
            raise ValueError("rate/n_keys must be positive, max_delay >= 0")
        self.seed = seed
        self.n_keys = n_keys
        self.rate = rate
        self.late_prob = late_prob
        self.max_delay = max_delay
        self.total = total

    def _event(self, i: int) -> tuple:
        rng = random.Random((self.seed << 24) ^ i)
        ts = i // self.rate
        if self.max_delay and rng.random() < self.late_prob:
            ts = max(0, ts - rng.randint(1, self.max_delay))
        return (ts, f"k{rng.randrange(self.n_keys)}", rng.randrange(1000))

    # ------------------------------------------------------ source contract
    def initial(self) -> int:
        return 0

    def next_offset(self, offset: int, limit: int) -> int:
        end = offset + limit
        return end if self.total is None else min(end, self.total)

    def read(self, start: int, end: int) -> list:
        return [self._event(i) for i in range(start, end)]

    def exhausted(self, offset: int) -> bool:
        return self.total is not None and offset >= self.total


class S3PrefixTailer:
    """Tail an object-store prefix as an unbounded CSV stream: every new
    object under ``prefix`` becomes part of some micro-batch, rows parsed
    with the schema's CSV casts. The offset is the tuple of consumed
    object keys IN CONSUMPTION ORDER — ``next_offset`` appends newly
    listed keys (sorted, capped at ``limit``), and ``read`` re-fetches
    exactly the keys one offset added over the other, which makes replay
    exact as long as objects are immutable once written (the S3 model).

    ``seal()`` declares that no further objects will arrive, letting a
    finite stream drain (close every window) instead of idling."""

    def __init__(self, store, prefix: str, schema):
        self.store = store
        self.prefix = prefix
        self.schema = schema if isinstance(schema, Schema) else Schema(schema)
        self._casts = [scan_cast(t) for _, t in self.schema]
        self._sealed = False

    def seal(self) -> None:
        self._sealed = True

    def _parse(self, data: bytes) -> list:
        rows = []
        for line in data.decode("utf-8").splitlines():
            if line:
                rows.append(tuple(cast(f) for cast, f in
                                  zip(self._casts, line.split(","))))
        return rows

    # ------------------------------------------------------ source contract
    def initial(self) -> tuple:
        return ()

    def next_offset(self, offset: tuple, limit: int) -> tuple:
        consumed = set(offset)
        listed = ride_faults(self.store.list, self.prefix)
        new = [k for k in listed if k not in consumed]
        return tuple(offset) + tuple(new[:limit])

    def read(self, start: tuple, end: tuple) -> list:
        if tuple(end[:len(start)]) != tuple(start):
            raise ValueError("tailer offsets diverged: end does not "
                             "extend start")
        rows = []
        for key in end[len(start):]:
            rows.extend(self._parse(ride_faults(self.store.get, key)))
        return rows

    def exhausted(self, offset: tuple) -> bool:
        if not self._sealed:
            return False
        consumed = set(offset)
        listed = ride_faults(self.store.list, self.prefix)
        return all(k in consumed for k in listed)
