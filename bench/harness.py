"""One run of one benchmark cell: set-up, a measured window, the check
against the plain reference, and the metrics of ``BENCHMARK.json``.

Everything a cell is made of is a file found by name:

* the cell in ``BENCHMARK.json``, and its configuration in the
  configuration's ``file``: a JSON object naming its ``dataset``, its
  ``deployment``, its ``rows`` and ``input_partitions`` and the engine's
  settings;
* the dataset generator ``bench/datasets/<dataset>.py``: ``TABLE`` (the
  object key), ``SCHEMA`` ((name, dtype) of each CSV column) and
  ``generate(n_rows, seed)`` (the CSV bytes, a pure function of seed);
* the deployment ``bench/deployments/<deployment>.py``:
  ``open_deployment(config, table, data)``, an object with ``clients``
  (one closed-loop client each, with the program's ``read_csv``),
  ``device_stats(client)``, ``counters()`` and ``close()``;
* the traffic ``bench/traffic/<traffic>.json``, a mix of query specs
  (``bench/queries.py``), or ``bench/traffic/<traffic>.py``, whose
  ``mix(dataset)`` returns the same dict with query objects of its own;
* each metric's reader ``bench/metrics/<metric>.py``: ``read(run)`` of
  the dict that ``run_cell`` fills, returning a number, or None where it
  finds nothing to read.

``run`` holds:

* ``setup_s``: seconds from process start to the window's start;
* ``window_s``: seconds from the window's start to its last answer;
* ``latencies``: seconds from submit to collected result, per query;
* ``queries``, ``rows_scanned``: queries completed in the window, and
  the table rows they covered;
* ``device_stats``: their summed kernel_calls / x64_sums /
  device_fallbacks;
* ``counters``: the growth of the deployment's ``counters()`` over the
  window (the CostLedger's requests, GB-seconds and ``total_usd``);
* ``trace``: ``bench.trace.reduce_trace`` of the window (trace runs);
* ``grouped_sum_bytes``: ``bench.work`` bytes of the queries that summed
  on the device (trace runs);
* ``peaks``: the device's entry of ``bench/peaks.json`` (trace runs).
"""

from __future__ import annotations

import contextlib
import functools
import importlib.util
import json
import sys
import tempfile
import threading
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoAccelerator(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@functools.cache
def load_module(path: Path):
    """The Python file at ``path`` as a module of its own."""
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:])
    spec = importlib.util.spec_from_file_location(
        name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def dataset(name: str):
    return load_module(BENCH / "datasets" / f"{name}.py")


def load_mix(name: str, data_mod, traffic_dir: Path = BENCH / "traffic"):
    """The traffic mix ``name`` over ``data_mod``'s table: a dict with
    ``order`` (and its parameters) and ``queries``, query objects."""
    from bench.queries import SpecQuery

    path = traffic_dir / f"{name}.json"
    if path.is_file():
        mix = load_json(path)
        mix["queries"] = [SpecQuery(q, data_mod.SCHEMA)
                          for q in mix["queries"]]
        return mix
    path = traffic_dir / f"{name}.py"
    if path.is_file():
        return load_module(path).mix(data_mod)
    raise FileNotFoundError(f"no traffic mix {name!r} in {traffic_dir}")


def cell_parts(workload: str) -> tuple:
    """(benchmark, cell, configuration, dataset module, traffic mix) of
    ``workload``."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT / conf["file"])
    data_mod = dataset(config["dataset"])
    return bench, cell, config, data_mod, load_mix(cell["traffic"], data_mod)


def metrics_of(bench: dict, workload: str, trace: bool) -> list:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]


def reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py").read


# ------------------------------------------------------------- traffic


def schedule(mix: dict, client: int, seed: int):
    """The endless query sequence (indices into ``mix["queries"]``) of one
    closed-loop client: the mix's own ``schedule(client, seed)`` where it
    has one, else its queries in turn (``"order": "alternate"``), the
    client's first query offset by its number."""
    if callable(mix.get("schedule")):
        return mix["schedule"](client, seed)
    if mix.get("order") != "alternate":
        raise ValueError(f"unknown order {mix.get('order')!r}")
    n = len(mix["queries"])
    return (i % n for i in range(client, 2**62))


def round_length(mix: dict) -> int:
    """Queries a client completes before it may stop: a whole round of an
    alternating mix, so that each of its queries runs equally often."""
    return len(mix["queries"]) if mix.get("order") == "alternate" else 1


# -------------------------------------------------------------- window


def _annotate(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def run_client(dep, client, table, mix, seq, deadline, records, lock):
    """One closed-loop client: submit, wait for the collected result,
    submit the next, until the deadline has passed and a round is whole."""
    round_len = round_length(mix)
    sent = 0
    while True:
        with _annotate("bench.between_queries"):
            if time.monotonic() >= deadline and sent % round_len == 0:
                return
            query = mix["queries"][next(seq)]
            sent += 1
        t0 = time.monotonic()
        try:
            with _annotate(f"bench.query.{query.name}"):
                rows = query.build(table).collect()
            err = None
        except Exception as e:  # a failed query is counted, not fatal
            rows, err = None, f"{type(e).__name__}: {e}"
        t1 = time.monotonic()
        rec = {"name": query.name, "t0": t0, "t1": t1, "rows": rows,
               "error": err,
               "device": dep.device_stats(client) if err is None else {}}
        with lock:
            records.append(rec)


def window(dep, tables, mix, seed, seconds) -> tuple:
    """Drive every client for ``seconds``; return (records, t_start,
    t_end), the window ending at the last answer."""
    records: list = []
    lock = threading.Lock()
    with _annotate("bench.window"):
        t_start = time.monotonic()
        deadline = t_start + seconds
        args = [(dep, c, tables[i], mix, schedule(mix, i, seed), deadline,
                 records, lock) for i, c in enumerate(dep.clients)]
        if len(args) == 1:
            run_client(*args[0])
        else:
            threads = [threading.Thread(target=run_client, args=a)
                       for a in args]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        t_end = max([r["t1"] for r in records], default=time.monotonic())
    return records, t_start, t_end


@contextlib.contextmanager
def profiled(enabled: bool):
    """Profile the block into a temporary directory; yields a holder that
    gets the reduced trace once the block ends."""
    holder: dict = {"trace": None}
    if not enabled:
        yield holder
        return
    import jax

    from bench import trace as tr

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            yield holder
        finally:
            jax.profiler.stop_trace()
        holder["trace"] = tr.reduce_trace(tr.load(d))


# --------------------------------------------------------------- check


def typed(rows) -> list:
    """Rows sorted, each value with its type: 1 and 1.0 differ."""
    return sorted(tuple((type(v).__name__, v) for v in r) for r in rows)


def compare(records, answers: dict, nkeys: dict) -> dict:
    """The numbers the check compares, each with its limit: answers that
    never came or differ from the reference, and the widest gap of a
    number in a row whose keys the reference has."""
    wrong, gap = 0, 0
    for rec in records:
        want = answers[rec["name"]]
        if rec["rows"] is None or typed(rec["rows"]) != typed(want):
            wrong += 1
        k = nkeys[rec["name"]]
        by_key = {r[:k]: r for r in want}
        for r in rec["rows"] or ():
            ref = by_key.get(r[:k])
            if ref is None or len(ref) != len(r):
                continue
            for a, b in zip(r[k:], ref[k:]):
                gap = max(gap, abs(a - b))
    return {"wrong_answers": {"value": wrong, "limit": 0},
            "max_abs_gap": {"value": gap, "limit": 0}}


def within(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


# ----------------------------------------------------------------- run


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
    log(f"platform {d.platform}, device_kind {d.device_kind}, "
        f"devices {len(devices)}")
    if require_tpu and (d.platform != "tpu" or len(devices) < chips):
        raise NoAccelerator(f"the cell needs {chips} TPU chip(s); JAX "
                            f"found {len(devices)} {d.platform} device(s)")
    return info


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, rows: int | None = None,
             require_tpu: bool = True) -> dict:
    """One run of ``workload``; returns the result line's object."""
    import jax

    from bench import reference, work

    bench, cell, config, data_mod, mix = cell_parts(workload)
    info = device_info(cell["chips"], require_tpu)
    phases = {"start": time.monotonic() - t_process}
    n_rows = rows or config["rows"]
    nparts = config["input_partitions"]
    t = time.monotonic()
    data = data_mod.generate(n_rows, seed)
    phases["data"] = time.monotonic() - t
    log(f"data: {n_rows} rows, {len(data)} bytes, seed {seed}")
    t = time.monotonic()
    dep = load_module(BENCH / "deployments"
                      / f"{config['deployment']}.py").open_deployment(
        config, data_mod.TABLE, data)
    schema = list(data_mod.SCHEMA)
    tables = [functools.partial(c.read_csv, data_mod.TABLE, schema, nparts)
              for c in dep.clients]
    phases["deploy"] = time.monotonic() - t
    t = time.monotonic()
    with _annotate("bench.warmup"):
        for query in mix["queries"]:
            query.build(tables[0]).collect()
    phases["warmup"] = time.monotonic() - t
    setup_s = time.monotonic() - t_process
    log(f"set-up: {setup_s:.3f} s ("
        + ", ".join(f"{k} {v:.3f}" for k, v in phases.items()) + ")")

    counters0 = dep.counters()
    with profiled(trace) as prof:
        records, t_start, t_end = window(dep, tables, mix, seed, seconds)
    counters1 = dep.counters()
    info["memory_peak_bytes"] = memory_peak(jax.devices()[:cell["chips"]])
    dep.close()
    del dep, tables

    # the check: the plain reference over the same bytes, once per query
    parsed = reference.parse(data)
    queries = {q.name: q for q in mix["queries"]}
    answers = {name: queries[name].reference(parsed)
               for name in {r["name"] for r in records}}
    checks = compare(records, answers,
                     {name: q.nkeys for name, q in queries.items()})
    done = [r for r in records if r["error"] is None]
    failed = len(records) - len(done)
    correct = bool(records) and within(checks) and failed == 0

    dstats: dict = {}
    for r in done:
        for k, v in r["device"].items():
            dstats[k] = dstats.get(k, 0) + v
    run = {
        "setup_s": setup_s,
        "window_s": t_end - t_start,
        "latencies": [r["t1"] - r["t0"] for r in done],
        "queries": len(done),
        "rows_scanned": n_rows * len(done),
        "device_stats": dstats,
        "counters": {k: counters1[k] - counters0[k] for k in counters0},
        "trace": prof["trace"],
        "grouped_sum_bytes": None,
        "peaks": None,
    }
    if trace:
        run["peaks"] = work.peaks(info["kind"]) if require_tpu else None
        parts = work.partition_of_rows(data, nparts)
        per_query = {name: queries[name].sum_bytes(parsed, parts)
                     for name in answers}
        summed = [per_query[r["name"]] for r in done
                  if r["device"].get("kernel_calls", 0)
                  + r["device"].get("x64_sums", 0)]
        if None not in summed:
            run["grouped_sum_bytes"] = sum(summed)
        tr = run["trace"]
        info["busy_s"] = tr["busy_s"] if tr else None
        info["window_s"] = tr["window_s"] if tr else run["window_s"]

    metrics = {}
    for m in metrics_of(bench, workload, trace):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": info}
    tr = run["trace"]
    if tr:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in tr["device_ops"]],
            "idle_gaps": [[n, s] for n, s in tr["idle_gaps"]]}
    log(f"window: {len(records)} queries in {run['window_s']:.3f} s, "
        f"{failed} failed, slowest "
        f"{max(run['latencies'], default=0.0):.3f} s, device {dstats}")
    for r in records:
        if r["error"]:
            log(f"failed {r['name']}: {r['error']}")
    result["checks"] = checks
    return result
