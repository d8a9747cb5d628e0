"""The chip benchmark's harness: one cell's set-up, its measured window, and
the run record the checks and the metric readers read.

A cell is a configuration (``configs/<name>.json``: the deployment) under a
traffic mix (``traffic/<name>.json``).  Everything a cell needs is found by
the names in ``BENCHMARK.json``; nothing here names a cell.

Set-up restores the index from ``.cache/index/`` (built once per checkout
by the program's ``bulk_build`` from the configuration's corpus), starts a
WAL-backed ``StreamingEngine`` whose log fsyncs every batch before it is
acknowledged, warms every program the window will run, and draws the
run's queries and write stream from ``--seed``.

The window drives the served entry points only: ``ServeFrontend.submit``
for queries and ``ServeFrontend.submit_mutations`` for writes.  Each query
is timed from its due time to the moment its answer is in the client's
hands.
"""
from __future__ import annotations

import collections
import dataclasses
import faulthandler
import hashlib
import importlib.util
import json
import queue
import shutil
import sys
import threading
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "chipbench"
CACHE = BENCH / ".cache"
sys.path.insert(0, str(ROOT / "src"))

TREE_META = ("capacity", "dim", "metric", "max_nodes", "min_fill")
WAIT_PAST_CLOSE_S = 60.0
WARM_BLOCK = 2**31     # query draws for warm-up, apart from any window's
WARM_BATCHES = 2       # writer batches a warm-up applies
STALL_S = 0.25         # a load-generator stall worth a stack dump
TAIL_S = 10.0          # open-loop load past the close, while a batch runs


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py``, imported by path (names may hold
    dots and dashes)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    mod_name = f"chipbench_{kind}_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(workload: str, spec: dict | None = None,
              traffic_dir: Path = BENCH / "traffic"):
    """(workload entry, configuration, traffic) of one cell."""
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    wl = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    cfg = load_json(ROOT / entry["file"])
    traffic = load_json(traffic_dir / f"{wl['traffic']}.json")
    return wl, cfg, traffic


# ---------------------------------------------------------------- index cache
# configuration keys that say how the index is served or checked, not what
# it holds: changing them keeps the cached index
SERVING_KEYS = ("k", "max_frontier", "limits", "guarantees", "assumed",
                "reduced", "source", "source_part", "deployment")


def _index_key(cfg: dict) -> str:
    """Config (what the index holds), the copied generator and the
    tree-building sources."""
    held = {k: v for k, v in cfg.items() if k not in SERVING_KEYS}
    h = hashlib.sha256(json.dumps(held, sort_keys=True).encode())
    files = [BENCH / "data" / f"{cfg['generator']}.py"]
    files += sorted((ROOT / "src" / "repro" / "core").glob("*.py"))
    for p in files:
        h.update(p.name.encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:16]


def _save_index(d: Path, tree, corpus: np.ndarray) -> None:
    import jax
    tmp = d.with_name(d.name + ".tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    tmp.mkdir(parents=True)
    for f in dataclasses.fields(tree):
        if f.name not in TREE_META:
            np.save(tmp / f"{f.name}.npy",
                    np.asarray(jax.device_get(getattr(tree, f.name))))
    np.save(tmp / "corpus.npy", corpus)
    meta = {m: getattr(tree, m) for m in TREE_META}
    (tmp / "meta.json").write_text(json.dumps(meta))
    tmp.rename(d)


def _load_tree(d: Path):
    import jax
    from repro.core.smtree import TreeArrays
    meta = load_json(d / "meta.json")
    arrays = {f.name: np.load(d / f"{f.name}.npy")
              for f in dataclasses.fields(TreeArrays)
              if f.name not in TREE_META}
    return TreeArrays(**jax.device_put(arrays), **meta)


def index(cfg: dict, gen, log=print):
    """(tree, index dir, built?) — restored from the cache, or built by the
    program's ``bulk_build`` from the corpus and cached."""
    from repro.core import smtree
    d = CACHE / "index" / f"{cfg['name']}-{_index_key(cfg)}"
    if (d / "meta.json").is_file():
        return _load_tree(d), d, False
    for old in (CACHE / "index").glob(f"{cfg['name']}-*"):
        shutil.rmtree(old, ignore_errors=True)
    t = time.perf_counter()
    corpus = gen.corpus(cfg)
    tree = smtree.bulk_build(corpus, capacity=cfg["capacity"],
                             metric=cfg["metric"], seed=cfg["data_seed"])
    log(f"index: built {cfg['name']} ({len(corpus)} x {cfg['dim']}) in "
        f"{time.perf_counter() - t:.1f}s: {tree.max_nodes} node slots, "
        f"height {int(tree.height)}")
    _save_index(d, tree, corpus)
    return tree, d, True


# ---------------------------------------------------------------- arrivals
def open_loop_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of a Poisson stream at
    ``rate``: round(rate * seconds) arrivals whose gaps are the exponential
    law's quantiles, in an order drawn from the seed.  Every seed sends the
    same number of queries with the same set of gaps; only the order (the
    bursts) differs."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = np.random.default_rng([seed, 6]).permutation(gaps)
    offs = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return offs[offs < seconds]


# ---------------------------------------------------------------- run record
@dataclasses.dataclass
class Record:
    """What one window did: the inputs it sent, the answers it got and
    when, and the writes that were acknowledged."""
    workload: str
    seed: int
    seconds: float
    t0: float = 0.0               # time.monotonic() at the window's start
    t_end: float = 0.0
    queries: np.ndarray | None = None      # [q, dim], in due order
    due: np.ndarray | None = None          # [q] monotonic s
    sent: np.ndarray | None = None         # [q] when submit returned
    done: np.ndarray | None = None         # [q] when the answer was in hand
    epoch: np.ndarray | None = None        # [q] epoch that answered
    dists: np.ndarray | None = None        # [q, k]
    ids: np.ndarray | None = None          # [q, k]
    failed: np.ndarray | None = None       # [q] bool: error or no answer
    trace_ids: list = dataclasses.field(default_factory=list)  # [q]
    batches: list = dataclasses.field(default_factory=list)  # (sub, ack, n)
    batch_errors: int = 0
    engine: object = None
    wal_dir: Path | None = None
    spans: list = dataclasses.field(default_factory=list)
    compiles: list = dataclasses.field(default_factory=list)
    gc_pauses: list = dataclasses.field(default_factory=list)  # (gen, s)
    frontend: dict = dataclasses.field(default_factory=dict)
    trace_path: Path | None = None
    stall_path: Path | None = None         # stacks of any generator stall
    trace_t0_ns: float | None = None       # the window's start, trace clock

    @property
    def n_applied(self) -> int:
        return len(self.batches)

    def answered_in_window(self) -> int:
        ok = ~self.failed
        return int(np.sum(ok & (self.done <= self.t_end)))

    def ops_acked_in_window(self) -> int:
        return int(sum(n for _, ack, n in self.batches if ack <= self.t_end))

    def latencies_s(self) -> np.ndarray:
        """Due-to-answer latency of every query due in the window; a query
        that failed or never came back counts as infinitely late."""
        return np.where(self.failed, np.inf, self.done - self.due)


class _GcClock:
    """Durations of the garbage collector's passes while ``armed``: a
    stall of the load generator can be told from a collection."""

    def __init__(self):
        import gc
        self.armed = False
        self.pauses: list[tuple[int, float]] = []
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if not self.armed:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))
            self._t = None


class _CompileCounter:
    """Counts the backend compiles (and persistent-cache loads) that start
    while ``armed``: there should be none inside a window."""

    def __init__(self):
        import jax
        self.armed = False
        self.seen: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.seen.append(str(kw.get("fun_name", "?")))


# ---------------------------------------------------------------- the cell
class Cell:
    """One cell, set up once; ``window()`` may then run several times (the
    sweep and the control read many seeds in one process)."""

    def __init__(self, wl: dict, cfg: dict, traffic: dict, *, log=print):
        self.log = log
        self.wl, self.cfg, self.traffic = wl, cfg, traffic
        self.name = wl["name"]
        self.gen = load_module("data", self.cfg["generator"])
        self.reference = load_module("reference", self.cfg["metric"])
        self.tree0, self.index_dir, self.built = index(self.cfg, self.gen,
                                                       log)
        self._corpus = None
        self._stream = None
        self.compiles = _CompileCounter()
        self.gc = _GcClock()

    @classmethod
    def from_spec(cls, workload: str, spec: dict | None = None,
                  traffic_dir: Path = BENCH / "traffic", **kw):
        return cls(*cell_spec(workload, spec, traffic_dir), **kw)

    # -- inputs ------------------------------------------------------------
    def corpus(self) -> np.ndarray:
        if self._corpus is None:
            self._corpus = np.load(self.index_dir / "corpus.npy")
        return self._corpus

    def writer(self) -> dict | None:
        return self.traffic.get("writer")

    def stream(self, seed: int):
        """The seed's churn stream (victims found on the host by the
        plain reference's metric); the last seed's is kept."""
        if self._stream is None or self._stream.seed != seed:
            w = self.writer()
            gen = load_module("data", w["stream"])
            self._stream = gen.Stream(self.corpus(), w, seed,
                                      self.reference.pairwise)
        return self._stream

    def queries(self, seed: int, n: int, block: int = 0) -> np.ndarray:
        return self.gen.queries(self.corpus(), seed, n, block)

    def frontend_config(self):
        from repro.serve.frontend import FrontendConfig
        # cohort width and SLO stay at the front end's defaults
        return FrontendConfig(k=self.cfg["k"],
                              max_frontier=self.cfg["max_frontier"])

    # -- warm-up -----------------------------------------------------------
    def warm(self, seed: int, *, level_stats: bool) -> None:
        """Run every program this cell's window runs, at its shapes, on
        throwaway state: the query cohort (and, for traced runs, the
        level-stats variant obs samples), and for a writer the mutation
        scan at the batch's width and the split and merge chunk widths."""
        import jax
        from repro.core import smtree
        from repro.serve.frontend import ServeFrontend
        from repro.stream import StreamingEngine
        fcfg = self.frontend_config()
        Q = self.queries(seed, fcfg.cohort_width, WARM_BLOCK)
        with ServeFrontend(StreamingEngine(self.tree0), fcfg) as fe:
            fe.knn(Q, timeout=900)
        if level_stats:
            r, _ = smtree.knn(self.tree0, Q, k=fcfg.k,
                              max_frontier=fcfg.max_frontier,
                              level_stats=True)
            jax.block_until_ready(r.dists)
        if self.writer() is None:
            return
        stream = self.stream(seed)
        eng = StreamingEngine(self.tree0)
        for b in range(WARM_BATCHES):
            eng.apply(*stream.batch(b))
        # every chunk width the split/merge passes dispatch, as NOP rows,
        # on a copy: these calls donate their input, and a jitted step may
        # hand back unchanged input arrays (the initial tree's) as its own
        t = jax.tree.map(lambda a: a.copy(), eng.tree)
        del eng
        nop = np.zeros(smtree.SPLIT_CHUNK, np.int32)
        t, _ = smtree.apply_splits(
            t, nop, np.zeros((len(nop), t.dim), np.float32), nop - 1,
            donate=True)
        for w in sorted({smtree.MERGE_CHUNK, smtree.MERGE_CHUNK_MAX}):
            nop = np.zeros(w, np.int32)
            t, st = smtree.apply_merges(t, nop, nop - 1, donate=True)
        jax.block_until_ready(st)

    # -- the window --------------------------------------------------------
    def window(self, seed: int, seconds: float, *, rate: float | None = None,
               trace_dir: Path | None = None) -> Record:
        """Serve the cell's traffic for ``seconds``; returns its record.
        ``rate`` overrides the traffic's open-loop rate (the knee sweep);
        ``trace_dir`` turns on the profiler and obs spans."""
        from repro import obs
        from repro.serve.frontend import ServeFrontend
        from repro.stream import StreamingEngine, WriteAheadLog

        rec = Record(self.name, seed, seconds)
        traffic = self.traffic
        wal_dir = CACHE / "wal" / self.name
        shutil.rmtree(wal_dir, ignore_errors=True)
        rec.wal_dir = wal_dir
        rec.stall_path = CACHE / "stalls" / f"{self.name}.txt"
        eng = StreamingEngine(self.tree0,
                              wal=WriteAheadLog(str(wal_dir), sync=True))
        rec.engine = eng
        fcfg = self.frontend_config()
        fe = ServeFrontend(eng, fcfg)
        arrivals = traffic["arrivals"]       # "open" or "closed"
        if arrivals == "closed":
            qs = self.queries(seed, traffic["clients"] * 4)
        else:
            offs = open_loop_offsets(rate or traffic["rate_qps"], seconds,
                                     seed)
            qs = self.queries(seed, len(offs))
        stream = self.stream(seed) if self.writer() else None
        if stream is not None and arrivals == "open":
            # the writer's last batch, sent before the close, is timed
            # under the same query load as the others: arrivals go on past
            # the close until it is acknowledged, served but not recorded
            tail = seconds + open_loop_offsets(rate or traffic["rate_qps"],
                                               TAIL_S, seed)
            tail_qs = self.queries(seed, len(tail), 1)

        spans = []
        if trace_dir is not None:
            obs.enable()
            obs.reset()
            sink = obs.trace.GATE.sink
            obs.trace.GATE.sink = lambda s: (spans.append(s), sink(s))
        fe.start()
        prof = None
        try:
            if trace_dir is not None:
                prof = _Profiler(trace_dir)
            rec.t0 = time.monotonic() + 0.05
            rec.t_end = rec.t0 + seconds
            if prof is not None:
                prof.mark(rec.t0)
            self.compiles.armed = self.gc.armed = True
            writer = None
            if stream is not None:
                writer = threading.Thread(target=_write_loop,
                                          args=(fe, stream, rec),
                                          name="chipbench-writer")
                writer.start()
            if arrivals == "closed":
                got = _closed_loop(fe, qs, traffic["clients"], rec, self,
                                   seed)
            else:
                got = _open_loop(fe, qs, rec.t0 + offs, rec, tail=(
                    (tail_qs, rec.t0 + tail, writer.is_alive)
                    if writer is not None else None))
            if writer is not None:
                writer.join()
            self.compiles.armed = self.gc.armed = False
            rec.compiles = list(self.compiles.seen)
            self.compiles.seen.clear()
            rec.gc_pauses, self.gc.pauses = self.gc.pauses, []
            # every ticket is answered or given up on by now; what is
            # still queued or running past the wait is failed below
            try:
                fe.drain(timeout=WAIT_PAST_CLOSE_S)
            except TimeoutError:
                pass
            if prof is not None:
                rec.trace_path, rec.trace_t0_ns = prof.stop()
                prof = None
        finally:
            self.compiles.armed = self.gc.armed = False
            if prof is not None:
                prof.stop()
            fe.stop(drain=False)
            if trace_dir is not None:
                obs.trace.GATE.sink = sink
                obs.disable()
        (rec.queries, rec.due, rec.sent, rec.done, rec.epoch, rec.dists,
         rec.ids, rec.failed, rec.trace_ids) = got
        rec.spans = [s.to_dict() for s in spans]
        rec.frontend = fe.stats.snapshot()
        return rec


class _Profiler:
    """JAX's profiler over the window, with the window's start marked on
    the host line so obs spans (``time.monotonic``) map onto its clock."""

    MARK = "chipbench.window_start"

    def __init__(self, trace_dir: Path):
        import jax
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        self.dir = trace_dir
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no per-call Python events
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)

    def mark(self, t0: float) -> None:
        import jax
        while time.monotonic() < t0:
            pass
        with jax.profiler.TraceAnnotation(self.MARK):
            pass

    def stop(self):
        import jax
        jax.profiler.stop_trace()
        path = next(self.dir.rglob("*.xplane.pb"), None)
        t0_ns = None
        if path is not None:
            from chipbench.trace import find_mark
            t0_ns = find_mark(path, self.MARK)
        return path, t0_ns


def _wait_until(t: float) -> None:
    """Sleep until ``t``; the last 0.2 ms in yields, not in one sleep
    (which can overshoot by the scheduler's slack)."""
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(left - 0.0002 if left > 0.0004 else 0)


def _collect(pending: "queue.Queue", n_hint: int, t_end: float, out: list):
    """Collector thread: takes tickets in submission order (the front end
    answers in that order) and stamps when each answer is in hand."""
    while True:
        item = pending.get()
        if item is None:
            return
        i, tk = item
        left = max(0.0, t_end + WAIT_PAST_CLOSE_S - time.monotonic())
        try:
            d, ids = tk.result(timeout=left)
            out.append((i, time.monotonic(), tk.epoch, d, ids, False))
        except Exception:  # noqa: BLE001 — a failed answer is a failure
            out.append((i, time.monotonic(), -1, None, None, True))


def _pack(n: int, qs, due, sent, answers, trace_ids, k: int):
    done = np.full(n, np.inf)
    epoch = np.full(n, -1, np.int64)
    dists = np.full((n, k), np.inf, np.float32)
    ids = np.full((n, k), -1, np.int32)
    failed = np.ones(n, bool)
    for i, t, e, d, ii, bad in answers:
        done[i] = t
        if not bad:
            epoch[i], dists[i], ids[i], failed[i] = e, d, ii, False
    return (qs[:n], np.asarray(due[:n]), np.asarray(sent[:n]), done, epoch,
            dists, ids, failed, list(trace_ids[:n]))


def _open_loop(fe, qs, due, rec: Record, tail=None):
    """Submit query i at due[i], whatever the system is doing.  Should the
    generator not come back for ``STALL_S``, the interpreter's watchdog
    (a native thread that needs no interpreter lock) writes every thread's
    stack to ``rec.stall_path``: what held the process up.  ``tail``, a
    ``(queries, due, alive)`` triple, goes on after the last recorded
    query, unrecorded, while ``alive()``."""
    pending: queue.Queue = queue.Queue()
    answers: list = []
    col = threading.Thread(target=_collect,
                           args=(pending, len(due), rec.t_end, answers),
                           name="chipbench-collector")
    col.start()
    sent = np.zeros(len(due))
    trace_ids = []
    rec.stall_path.parent.mkdir(parents=True, exist_ok=True)
    with open(rec.stall_path, "w") as stall:
        try:
            for i in range(len(due)):
                faulthandler.dump_traceback_later(
                    max(0.0, due[i] - time.monotonic()) + STALL_S,
                    file=stall)
                _wait_until(due[i])
                tk = fe.submit(qs[i])
                sent[i] = time.monotonic()
                trace_ids.append(tk.trace_id)
                pending.put((i, tk))
            faulthandler.cancel_dump_traceback_later()
            if tail is not None:
                _load_while(fe, *tail)
        finally:
            faulthandler.cancel_dump_traceback_later()
            pending.put(None)
            col.join()
    return _pack(len(due), qs, due, sent, answers, trace_ids,
                 fe.cfg.k)


def _load_while(fe, qs, due, alive) -> None:
    """Submit query i at due[i] while ``alive()``; the answers are left to
    the front end's drain."""
    for i in range(len(due)):
        if not alive():
            return
        _wait_until(due[i])
        fe.submit(qs[i])


def _closed_loop(fe, qs, clients: int, rec: Record, cell: Cell, seed: int):
    """``clients`` callers, each sending its next query the moment its last
    answer arrives; a query is due when its caller sends it.  The front end
    answers in submission order, so the oldest ticket is always next."""
    outstanding: collections.deque = collections.deque()
    due, sent, answers, trace_ids, rows = [], [], [], [], []
    block = 0

    def send(t_due):
        nonlocal qs, block
        i = len(due)
        j = i - block * len(qs)
        if j == len(qs):            # a fresh block of draws, no repeats
            block += 1
            qs = cell.queries(seed, len(qs), block)
            j = 0
        q = qs[j]
        rows.append(q)
        due.append(t_due)
        tk = fe.submit(q)
        sent.append(time.monotonic())
        trace_ids.append(tk.trace_id)
        outstanding.append((i, tk))

    _wait_until(rec.t0)
    for _ in range(clients):
        send(rec.t0)
    while outstanding:
        i, tk = outstanding.popleft()
        left = max(0.0, rec.t_end + WAIT_PAST_CLOSE_S - time.monotonic())
        try:
            d, ids = tk.result(timeout=left)
            t = time.monotonic()
            answers.append((i, t, tk.epoch, d, ids, False))
        except Exception:  # noqa: BLE001 — a failed answer is a failure
            t = time.monotonic()
            answers.append((i, t, -1, None, None, True))
        if t < rec.t_end:
            send(t)
    return _pack(len(due), np.asarray(rows), due, sent, answers, trace_ids,
                 fe.cfg.k)


def _write_loop(fe, stream, rec: Record) -> None:
    """One closed-loop writer: the next batch goes in when the last one is
    acknowledged (WAL fsync, apply, epoch publish)."""
    _wait_until(rec.t0)
    b = 0
    while time.monotonic() < rec.t_end:
        ops, xs, oids = stream.batch(b)
        t_sub = time.monotonic()
        try:
            tk = fe.submit_mutations(ops, xs, oids)
            stream.prepare(b + 2)       # the next batch, while this one runs
            tk.result(timeout=rec.t_end + WAIT_PAST_CLOSE_S - t_sub)
        except Exception:  # noqa: BLE001 — counted; the check fails it
            rec.batch_errors += 1
            return
        rec.batches.append((t_sub, time.monotonic(), len(ops)))
        b += 1
