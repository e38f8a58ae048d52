"""The streaming DataFrame surface and the micro-batch driver loop.

    src = EventGenerator(seed=7, total=2000)
    q = (read_stream(ctx, src)
         .where(col("val") >= lit(10))
         .window("ts", size=20, slide=10)
         .groupBy("key")
         .agg(sum_(col("val")).alias("total"), count_().alias("n"))
         .start("demo", allowed_lateness=5, batch_size=300))
    rows = q.run()        # finalized (w_start, w_end, key, total, n)
    q.cleanup()

Each micro-batch is an ORDINARY job: the driver snapshots source
offsets, replays the recorded transforms over ``ctx.parallelize`` of the
batch rows, appends the ``Window`` pane assignment and a pane-keyed
aggregation, and runs it through the stock optimize/lower/run_action
path — CSE, adaptive execution, vectorization and chaos recovery all
compose for free. Aggregates are decomposed into ALGEBRAIC SLOTS
(avg -> sum+count; count merges by addition) so per-batch, per-pane
partials merge associatively on the driver across batches, exactly like
the map-side combine merges partials across partitions. Two hidden
slots ride along: the pane's max event time (folded into a
``core.queues.watermark_message`` that advances the window frontier)
and its row count (late-data drop accounting).

Offsets + window state + emitted rows checkpoint atomically to one
content-addressed ``_stream/<name>/ckpt/<batch>`` object after every
batch (last two retained). Starting a query whose name has checkpoints
RESUMES from the newest readable one; with replayable sources that makes
a kill/restart exactly-once — the interrupted batch re-reads the same
offset range against the same pre-batch state. ``sink_to_prefix`` writes
one object per finalized window under deterministic keys, so replayed
emissions overwrite themselves idempotently; ``for_each_batch``
callbacks are at-least-once across a crash.

The per-batch shuffle transport is the cost model's SQS-vs-S3 call
(core.costs.pick_shuffle_transport) over an EWMA of observed window
volume — small hot windows ride the queue, large cold ones the object
store — unless pinned with ``transport=``. Under a service session
(repro.svc) the query admits ONCE as a long-running job
(``stream_begin``) and re-checks the tenant quota between batches.

See docs/streaming.md for the protocol write-up.
"""

from __future__ import annotations

import decimal
import operator
import time

from repro.core import costs
from repro.core.retry import TransientServiceError
from repro.core.queues import watermark_message, watermark_ts
from repro.core.scheduler import STREAM_PREFIX
from repro.sql import plan as P
from repro.sql.dataframe import DataFrame, _named
from repro.sql.expr import (AggExpr, Alias, Col, col, count_, decimal_scale,
                            div_half_up, max_, unscaled)
from repro.sql.optimizer import PARTIAL_COMBINE_FACTOR, _row_width
from repro.streaming.sources import ride_faults
from repro.streaming.windows import WindowSpec, WindowState

#: reserved output columns of the per-batch plan
PANE_COL = "__pane"
_WM_COL = "__wm"
_N_COL = "__n"

_SLOT_MERGE = {"sum": operator.add, "count": operator.add,
               "min": min, "max": max}


def read_stream(ctx, source) -> "StreamFrame":
    """Open a streaming frame over an unbounded source (repro.streaming.
    sources contract). The same transforms as a batch DataFrame apply;
    ``window().groupBy().agg()`` then defines the windowed aggregation a
    ``start()`` call turns into a running ``StreamingQuery``."""
    return StreamFrame(ctx, source)


class _ProtoRdd:
    """Placeholder lineage node for schema validation only — the proto
    plan is never lowered; each batch builds a real ParallelCollection."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.nparts = 1


def _decompose(named_aggs, schema):
    """Split user aggregates over ``schema`` into algebraic slots that
    merge across batches: returns (slot AggExprs, per-slot combiners,
    finalize fn). ``collect_list`` is holistic — it cannot merge as a
    fixed-width slot and is rejected for streams."""
    slots, merges, layout = [], [], []
    for name, a in named_aggs:
        off = len(slots)
        if a.op == "collect_list":
            raise ValueError(f"{name}: collect_list is not algebraic — "
                             f"unsupported in streaming aggregations")
        if a.op == "avg":
            slots.append(AggExpr("sum", a.child, name=f"__s{off}"))
            slots.append(AggExpr("count", None, name=f"__s{off + 1}"))
            merges += [_SLOT_MERGE["sum"], _SLOT_MERGE["count"]]
            layout.append((_merged_avg(a, schema), off))
        else:  # sum/count/min/max merge with their own combiner
            slots.append(AggExpr(a.op, a.child, name=f"__s{off}"))
            merges.append(_SLOT_MERGE[a.op])
            layout.append((None, off))

    def finalize(vals):
        return [vals[off] if avg is None else avg(vals[off], vals[off + 1])
                for avg, off in layout]
    return slots, merges, finalize


def _merged_avg(a: AggExpr, schema):
    """(merged sum, count) -> avg as a batch query answers it: a float,
    or for a decimal, whose merged sum arrives as a ``decimal.Decimal``,
    the quotient rounded half up at the avg's scale (s + 4)."""
    scale = decimal_scale(a.dtype(schema))
    if scale is None:
        return operator.truediv

    def avg(total, count):
        q = div_half_up(unscaled(total, scale), count)
        return decimal.Decimal(f"{q}E-{scale}")
    return avg


class StreamFrame:
    """Pre-window transforms over the stream, validated eagerly against
    a proto plan (same plan nodes, never executed)."""

    def __init__(self, ctx, source, ops: tuple = (), proto: P.Plan = None):
        self.ctx = ctx
        self.source = source
        self.ops = tuple(ops)
        self.proto = proto if proto is not None else \
            P.RddScan(_ProtoRdd(ctx), source.schema)

    def _derive(self, op, proto: P.Plan) -> "StreamFrame":
        proto.schema()  # eager validation, like DataFrame._derive
        return StreamFrame(self.ctx, self.source, self.ops + (op,), proto)

    @property
    def schema(self):
        return self.proto.schema()

    def where(self, pred) -> "StreamFrame":
        return self._derive(("where", pred), P.Filter(self.proto, pred))

    filter = where

    def withColumn(self, name: str, e) -> "StreamFrame":
        from repro.sql.expr import _as_expr
        e = _as_expr(e)
        if name in self.schema.names:
            cols = [(n, e if n == name else Col(n))
                    for n in self.schema.names]
        else:
            cols = [(n, Col(n)) for n in self.schema.names] + [(name, e)]
        return self._derive(("withColumn", name, e),
                            P.Project(self.proto, cols))

    def select(self, *cols) -> "StreamFrame":
        named = [_named(c, "select") for c in cols]
        return self._derive(("select", cols), P.Project(self.proto, named))

    def join(self, static: DataFrame, on, how: str = "inner"
             ) -> "StreamFrame":
        """STREAM-STATIC join: the static side is a bounded DataFrame
        from the same context, re-planned inside every micro-batch (CSE
        and cache() make repeats cheap). Only stream-preserving shapes
        are allowed — a right/outer join would re-emit unmatched static
        rows once per batch."""
        if how not in ("inner", "left"):
            raise ValueError(f"stream-static join supports how="
                             f"'inner'/'left', not {how!r}")
        on = [on] if isinstance(on, str) else list(on)
        return self._derive(("join", static, tuple(on), how),
                            P.Join(self.proto, static.plan, on, how=how))

    def window(self, ts_col: str, size: int, slide: int | None = None
               ) -> "WindowedStream":
        spec = WindowSpec(ts_col, size, slide)
        if PANE_COL in self.schema.names:
            raise ValueError(f"{PANE_COL!r} is reserved for the window "
                             f"pane column")
        proto = P.Window(self.proto, ts_col, spec.size, spec.slide,
                         name=PANE_COL)
        return WindowedStream(self, spec, proto)

    def for_each_batch(self, fn) -> "StreamingQuery":
        raise ValueError("for_each_batch attaches at start(); define the "
                         "windowed aggregation first: "
                         ".window(...).groupBy(...).agg(...)"
                         ".start(name, for_each_batch=fn)")


class WindowedStream:
    def __init__(self, frame: StreamFrame, spec: WindowSpec,
                 proto: P.Plan):
        self.frame = frame
        self.spec = spec
        self.proto = proto

    def groupBy(self, *keys) -> "WindowedGrouped":
        named = tuple(_named(k, "groupBy") for k in keys)
        return WindowedGrouped(self, named)


class WindowedGrouped:
    def __init__(self, ws: WindowedStream, keys: tuple):
        self.ws = ws
        self.keys = keys

    def agg(self, *aggs: AggExpr, numPartitions: int | None = None
            ) -> "StreamDef":
        if not aggs:
            raise ValueError("agg() needs at least one aggregate")
        named = []
        for a in aggs:
            if not isinstance(a, AggExpr):
                raise TypeError(f"agg() takes aggregate expressions, "
                                f"got {a!r}")
            named.append((a.name, a))
        slots, merges, finalize = _decompose(named, self.ws.proto.schema())
        spec = self.ws.spec
        ts = spec.ts_col
        batch_aggs = ([a.alias(a.name) for a in slots]
                      + [max_(col(ts)).alias(_WM_COL),
                         count_().alias(_N_COL)])
        # validate the full per-batch plan shape once, eagerly
        keys = ((PANE_COL, Col(PANE_COL)),) + self.keys
        P.Aggregate(self.ws.proto, keys,
                    [(a.name, a) for a in batch_aggs],
                    nparts=numPartitions).schema()
        return StreamDef(self.ws.frame, spec, self.keys, named, slots,
                         merges, finalize, numPartitions)


class StreamDef:
    """A fully-defined windowed streaming aggregation; ``start`` runs
    it (resuming from checkpoints under the same name, if any)."""

    def __init__(self, frame, spec, keys, named_aggs, slots, merges,
                 finalize, nparts):
        self.frame = frame
        self.spec = spec
        self.keys = keys
        self.named_aggs = named_aggs
        self.slots = slots
        self.merges = merges
        self.finalize = finalize
        self.nparts = nparts

    def start(self, name: str, *, allowed_lateness: int = 0,
              batch_size: int = 500, transport: str = "auto",
              sink_prefix: str | None = None, for_each_batch=None,
              checkpoint: bool = True) -> "StreamingQuery":
        return StreamingQuery(self, name,
                              allowed_lateness=allowed_lateness,
                              batch_size=batch_size, transport=transport,
                              sink_prefix=sink_prefix,
                              for_each_batch=for_each_batch,
                              checkpoint=checkpoint)


class StreamingQuery:
    """The micro-batch driver loop (see module docstring)."""

    def __init__(self, sdef: StreamDef, name: str, *,
                 allowed_lateness: int = 0, batch_size: int = 500,
                 transport: str = "auto", sink_prefix: str | None = None,
                 for_each_batch=None, checkpoint: bool = True):
        if batch_size <= 0:
            raise ValueError("batch_size must be positive")
        frame = sdef.frame
        self.ctx = frame.ctx
        self.store = frame.ctx.store
        self.source = frame.source
        self.ops = frame.ops
        self.spec = sdef.spec
        self.name = name
        self.batch_size = batch_size
        self.transport = transport
        self.sink_prefix = sink_prefix
        self.for_each_batch = for_each_batch
        self.checkpointing = checkpoint

        self._key_names = tuple(n for n, _ in sdef.keys)
        self._key_args = tuple(
            n if isinstance(e, Col) and e.name == n else Alias(e, n)
            for n, e in sdef.keys)
        self._slot_aggs = ([a.alias(a.name) for a in sdef.slots]
                           + [max_(col(self.spec.ts_col)).alias(_WM_COL),
                              count_().alias(_N_COL)])
        self._nslots = len(sdef.slots)
        self.nparts = sdef.nparts or 4
        # observed-volume estimate for the per-window transport choice:
        # post-transform row width (plus the pane int) x batch rows
        self._row_bytes = _row_width(frame.proto.schema()) + 8.0

        self.state = WindowState(self.spec, sdef.merges, sdef.finalize,
                                 allowed_lateness)
        self.offset = self.source.initial()
        self.batch = 0
        self.emitted: list = []
        self.wmarks: list = []      # (src, batch, event-time) per message
        self.transports: list = []  # cost-model choice per batch
        self._volume: float | None = None
        self._drained = False
        self._stopped = False

        # service integration: admit ONCE as a long-running job; the
        # slot is held until stop()/cleanup()
        self._svc = hasattr(self.ctx, "stream_begin")
        if self._svc:
            self.ctx.stream_begin()
        if self.checkpointing:
            self._resume()

    # ------------------------------------------------------------ plumbing
    @property
    def _ckpt_prefix(self) -> str:
        return f"{STREAM_PREFIX}{self.name}/ckpt/"

    def _resume(self) -> bool:
        """Restore from the newest READABLE checkpoint: a checkpoint the
        chaos plan ate (acknowledged write, lost object) simply does not
        list, so recovery falls back to its predecessor and the
        replayable source re-reads the lost batch — exactly-once."""
        for key in sorted(ride_faults(self.store.list, self._ckpt_prefix),
                          reverse=True):
            try:
                snap = ride_faults(self.store.get_obj, key)
            except Exception:  # unreadable checkpoint -> try the older one
                continue
            self.offset = snap["offset"]
            self.batch = snap["batch"]
            self.state.restore(snap["state"])
            self.emitted = list(snap["emitted"])
            self.wmarks = list(snap["wmarks"])
            self.transports = list(snap["transports"])
            self._volume = snap["volume"]
            self._drained = snap["drained"]
            return True
        return False

    def _checkpoint(self) -> None:
        if not self.checkpointing:
            return
        snap = {"version": 1, "batch": self.batch, "offset": self.offset,
                "state": self.state.snapshot(),
                "emitted": list(self.emitted),
                "wmarks": list(self.wmarks),
                "transports": list(self.transports),
                "volume": self._volume, "drained": self._drained}
        ride_faults(self.store.put_obj,
                    f"{self._ckpt_prefix}{self.batch:08d}", snap)
        old = self.batch - 2  # retain the last two checkpoints
        if old >= 0:
            self.store.delete(f"{self._ckpt_prefix}{old:08d}")

    def _choose_transport(self, nrows: int) -> str:
        if self.transport != "auto":
            choice = self.transport
        else:
            obs = nrows * self._row_bytes
            self._volume = obs if self._volume is None else \
                0.5 * self._volume + 0.5 * obs
            choice = costs.pick_shuffle_transport(
                self._volume * PARTIAL_COMBINE_FACTOR,
                self.nparts, self.nparts)
        self.transports.append(choice)
        return choice

    def _stage(self, rows: list):
        """``ctx.parallelize`` with per-attempt retry: a transient fault
        mid-staging abandons a PARTIAL collection (each attempt takes a
        fresh counter), so failed attempts are swept before retrying."""
        for i in range(8):
            key = f"_collections/{self.ctx._collection_counter}"
            try:
                return self.ctx.parallelize(rows, self.nparts)
            except TransientServiceError:
                self.store.delete_prefix(key + "/")
                time.sleep(min(0.25, 0.002 * (2 ** i)))
        return self.ctx.parallelize(rows, self.nparts)

    def _run_batch(self, rows: list) -> list:
        choice = self._choose_transport(len(rows))
        rdd = self._stage(rows)
        try:
            df = DataFrame.from_rdd(rdd, self.source.schema)
            for op in self.ops:
                df = self._apply(df, op)
            df = df.withWindow(self.spec.ts_col, self.spec.size,
                               self.spec.slide, name=PANE_COL)
            gd = df.groupBy(PANE_COL, *self._key_args)
            return gd.agg(*self._slot_aggs, numPartitions=self.nparts,
                          transport=choice).collect()
        finally:
            # batch staging data is job input, not engine state — drop it
            # as soon as the batch's job is done
            self.store.delete_prefix(rdd.key + "/")

    def _apply(self, df: DataFrame, op: tuple) -> DataFrame:
        kind = op[0]
        if kind == "where":
            return df.where(op[1])
        if kind == "withColumn":
            return df.withColumn(op[1], op[2])
        if kind == "select":
            return df.select(*op[1])
        if kind == "join":
            static, on, how = op[1], op[2], op[3]
            return df.join(DataFrame(df.ctx, static.plan), on=list(on),
                           how=how)
        raise ValueError(f"unknown stream op {kind!r}")

    def _deliver(self, finalized: list, batch_id: int) -> None:
        if not finalized:
            return
        self.emitted.extend(finalized)
        if self.sink_prefix is not None:
            by_window: dict = {}
            for r in finalized:
                by_window.setdefault((r[0], r[1]), []).append(r)
            for (ws, we), rows in by_window.items():
                # deterministic per-window keys: a post-crash replay
                # overwrites the same objects with the same bytes
                ride_faults(self.store.put_obj,
                            f"{self.sink_prefix.rstrip('/')}/"
                            f"w{ws}_{we}", rows)
        if self.for_each_batch is not None:
            self.for_each_batch(batch_id, list(finalized))

    # ------------------------------------------------------------ the loop
    def step(self) -> bool:
        """One micro-batch: snapshot offsets, run the batch job, merge
        pane partials, advance the watermark, deliver what closed,
        checkpoint. Returns True if the batch carried any rows."""
        if self._stopped:
            raise RuntimeError(f"streaming query {self.name!r} is stopped")
        if self._svc:
            self.ctx.stream_quota_check()
        start = self.offset
        end = self.source.next_offset(start, self.batch_size)
        rows = self.source.read(start, end) if end != start else []
        batch_id = self.batch
        wm = None
        if rows:
            nuser = len(self._key_names)
            for r in self._run_batch(rows):
                pane = r[0]
                key = tuple(r[1:1 + nuser])
                slots = list(r[1 + nuser:1 + nuser + self._nslots])
                bwm, nrows = r[-2], r[-1]
                self.state.merge(pane, key, slots, nrows)
                wm = bwm if wm is None else max(wm, bwm)
        if wm is not None:
            # fold this batch's max event time into the watermark
            # protocol — the streaming generalization of per-producer EOS
            msg = watermark_message(f"{self.name}/b{batch_id}", wm,
                                    batch_id)
            self.wmarks.append((msg.src, batch_id, watermark_ts(msg)))
            finalized = self.state.advance(watermark_ts(msg))
        else:
            finalized = self.state.advance(None)
        self.offset = end
        self.batch = batch_id + 1
        self._deliver(finalized, batch_id)
        self._checkpoint()
        return bool(rows)

    def drain(self) -> None:
        """Close EVERY remaining window — the infinite watermark that
        degenerates to the batch engine's EOS. Called by run() when a
        finite source reports exhaustion."""
        if self._drained:
            return
        src = f"{self.name}/drain"
        msg = watermark_message(src, float("inf"), self.batch)
        self.wmarks.append((src, self.batch, watermark_ts(msg)))
        finalized = self.state.advance(watermark_ts(msg))
        self._drained = True
        self._deliver(finalized, self.batch)
        self._checkpoint()

    def run(self, max_batches: int | None = None, drain: bool = True
            ) -> list:
        """Drive the loop until the source is exhausted (or max_batches
        ran), then optionally drain; returns finalized rows so far."""
        steps = 0
        while max_batches is None or steps < max_batches:
            if self._drained:
                break
            self.step()
            steps += 1
            if self.source.exhausted(self.offset):
                break
            if max_batches is None and steps > 1_000_000:
                raise RuntimeError("unbounded run(): pass max_batches")
        if drain and self.source.exhausted(self.offset):
            self.drain()
        return self.results()

    # ---------------------------------------------------------- inspection
    def results(self) -> list:
        return list(self.emitted)

    @property
    def watermark(self) -> float:
        return self.state.watermark

    @property
    def late_dropped(self) -> int:
        return self.state.late_dropped

    def stats(self) -> dict:
        return {"batches": self.batch, "watermark": self.state.watermark,
                "late_dropped": self.state.late_dropped,
                "transports": list(self.transports),
                "wmarks": list(self.wmarks),
                "emitted": len(self.emitted)}

    # ------------------------------------------------------------ lifecycle
    def stop(self) -> None:
        """Stop driving the query (the service admission slot releases);
        checkpoints REMAIN so a same-name start() resumes."""
        if not self._stopped:
            self._stopped = True
            if self._svc:
                self.ctx.stream_end()

    def cleanup(self) -> int:
        """Stop and delete the query's ``_stream/`` state; returns the
        number of checkpoint objects removed."""
        self.stop()
        return self.store.delete_prefix(f"{STREAM_PREFIX}{self.name}/")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False
