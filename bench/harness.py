"""Drive one cell of ``BENCHMARK.json``: set up, measure a window, check.

The harness is driven by data.  A cell names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); its correctness limit sits in
``bench/cells/<cell>.json``; every metric is read by
``bench/metrics/<metric>.py``.  A configuration names its architecture
(``"plain": "<name>"``), and ``bench/plain/<name>.py`` gives everything
that knows that architecture's weights, layers and widths: the program's
``ArchConfig``, the plain weights, their names in the program, the plain
reference and the count of needed work (``bench/plain/decoder.py`` says
what each is).  The harness, the check and the trace reduction reach the
architecture only through that module.  So adding a cell, mix,
configuration, architecture or metric adds files and entries, and edits
no file the benchmark already has.

One run, in one process that starts no child:

  1. set-up: the persistent compile cache, the weights made on the device
     from the seed, the program's paged engine over them (which compresses
     them where the configuration says so), a warm-up of the two step
     shapes the cell uses, and any state the mix needs (a resident mix
     prefills its contexts here);
  2. the window: the harness drives ``engine.submit``/``engine.tick``
     itself for ``seconds``, stamping every token at the end of the tick
     that returned it (``tick`` ends in a blocking ``device_get``);
  3. the metrics: ``--trace 0`` the cell's end-to-end metrics, ``--trace
     1`` its per-layer metrics, from a profiler trace of the same window;
  4. the check: with the engine freed, the plain reference scores a
     sample of what the window served (``bench/correct.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import re
import shutil
import sys
import time
import zlib
from pathlib import Path

import jax
import numpy as np

from bench import correct
from bench import model as bmodel
from bench import trace as btrace
from bench.traffic import Traffic
from bench.work import Need

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = Path(".bench_out") / "trace"  # under the cell's checkout
WARM_UID = 1 << 30  # warm-up requests' uids, clear of the traffic's
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SLOW_TICK_S = 0.5  # a tick of the cells' mixes takes 20-360 ms


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    plain: object  # the configuration's bench/plain/<name>.py module
    mix: dict
    check: dict
    end_to_end: list  # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    root: Path = ROOT  # where bench/metrics/ is read from


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    bench = _json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{[w['name'] for w in bench['workloads']]}")
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == entry["config"])
    conf = _json(root / conf_entry["file"])
    if "plain" not in conf:
        raise SystemExit(f"{conf_entry['file']} names no architecture: "
                         'give it "plain": "<name>" of bench/plain/<name>.py')
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    return Cell(
        name=name, chips=entry["chips"], conf=conf,
        plain=architecture(conf["plain"], root),
        mix=_json(root / "bench" / "traffic" / f"{entry['traffic']}.json"),
        check=_json(root / "bench" / "cells" / f"{name}.json"),
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"]
                   if _reports(m, name, names)],
        root=root)


def find_device(chips: int):
    """Device facts of a TPU host with ``chips`` chips, or (None, why)."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return None, f"JAX found no device: {e}"
    d = devices[0]
    if d.platform != "tpu":
        return None, (f"no TPU: JAX's first device is {d.platform} "
                      f"({d.device_kind}); the benchmark never runs there")
    if len(devices) < chips:
        return None, f"the cell needs {chips} chips, JAX found {len(devices)}"
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}, None


@functools.cache
def _module(path: Path, kind: str):
    """The file ``path`` as a module, registered (as dataclasses need)
    under a name of its own."""
    stem = re.sub(r"\W", "_", path.stem)
    name = f"bench_{kind}_{stem}_{zlib.crc32(str(path).encode()):08x}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def architecture(name: str, root: Path = ROOT):
    """The module ``root/bench/plain/<name>.py``, loaded once."""
    return _module((root / "bench" / "plain" / f"{name}.py").resolve(),
                   "plain")


def reader(metric: str, root: Path = ROOT):
    """``read`` of ``root/bench/metrics/<metric>.py``."""
    return _module((root / "bench" / "metrics" / f"{metric}.py").resolve(),
                   "metric").read


class Ticker:
    """Ticks the engine and records what each tick served.

    Wraps the engine instance's step callable and admission, so that each
    prefill and decode call, and each admission, is a host span
    (``bench.prefill``, ``bench.decode``, ``bench.admit``; a whole tick is
    ``bench.tick``).  The step wrapper waits for the step's outputs inside
    its span; the engine's own ``device_get`` follows at once anyway.
    """

    def __init__(self, engine, work: Need, clock=time.perf_counter):
        self.e = engine
        self.work = work
        self.clock = clock
        self.reqs: dict = {}  # uid -> traffic.Req
        self.due: dict = {}  # uid -> host time it was due
        self.stamps: dict = {}  # uid -> host time of each token
        self.calls: list[str] = []  # step kinds, in call order
        self.occupancy: list[float] = []
        self.longest_tick = 0.0
        # ticks over SLOW_TICK_S in the window: (wall, in the step call,
        # process CPU) seconds; a host that stood still shows wall time
        # outside the step that the process's CPU time does not cover
        self.slow: list[tuple[float, float, float]] = []
        self.in_step = 0.0
        self.counting = False
        step, admit = engine._step, engine._admit

        def wrapped_step(params, tokens, *rest):
            kind = "decode" if tokens.shape[1] == 1 else "prefill"
            self.calls.append(kind)
            t = self.clock()
            with jax.profiler.TraceAnnotation(f"bench.{kind}"):
                out = jax.block_until_ready(step(params, tokens, *rest))
            self.in_step += self.clock() - t
            return out

        def wrapped_admit():
            with jax.profiler.TraceAnnotation("bench.admit"):
                admit()

        engine._step, engine._admit = wrapped_step, wrapped_admit
        self.step_name = f"jit_{getattr(step, '__name__', '_step_fn')}("

    def submit(self, req, due: float) -> None:
        from repro.serve.engine import Request

        self.reqs[req.uid] = req
        self.due[req.uid] = due
        self.stamps[req.uid] = []
        self.e.submit(Request(uid=req.uid, prompt=req.prompt,
                              max_new_tokens=req.max_new))

    def _snapshot(self) -> dict:
        e = self.e
        return {r.uid: (e._pf_pos[s], len(e.generated[s]))
                for s, r in enumerate(e.req) if r is not None}

    def tick(self):
        """One engine tick; returns (end time, completions it made)."""
        e = self.e
        before, n_done = self._snapshot(), len(e.done)
        self.in_step, cpu = 0.0, time.process_time()
        start = self.clock()
        with jax.profiler.TraceAnnotation("bench.tick"):
            e.tick()
        now = self.clock()
        if self.counting and now - start > SLOW_TICK_S:
            self.slow.append((now - start, self.in_step,
                              time.process_time() - cpu))
        after = self._snapshot()
        new = e.done[n_done:]
        for c in new:
            after[c.uid] = (c.prompt_len, len(c.tokens))
        for uid, (pf, gen) in after.items():
            st = self.stamps.setdefault(uid, [])
            st += [now] * (gen - len(st))
            if not self.counting:
                continue
            pf0, gen0 = before.get(uid, (0, 0))
            plen = len(self.reqs[uid].prompt) if uid in self.reqs else pf
            if pf > pf0:
                self.work.prefill(pf0, pf, emits=pf == plen)
            for j in range(max(gen0, 1), gen):
                self.work.decode(plen + j - 1)
        if self.counting:
            self.occupancy.append(e.utilization)
            self.longest_tick = max(self.longest_tick, now - start)
        return now, new

    def drain(self) -> None:
        """Tick until the engine holds no work (set-up only)."""
        while self.e._has_work():
            self.tick()


def warm_up(ticker: Ticker, vocab: int, seed: int) -> None:
    """Compile and run both step shapes and admission once per slot: each
    slot prefills two chunks and decodes once."""
    e = ticker.e
    rng = np.random.default_rng([seed, 2])
    plen = e.geometry.prefill_chunk + 1
    from bench.traffic import Req

    for s in range(e.n_slots):
        ticker.submit(Req(uid=WARM_UID + s,
                          prompt=rng.integers(0, vocab, plen, dtype=np.int32),
                          max_new=2), due=0.0)
    ticker.drain()


def measure(ticker: Ticker, traffic: Traffic, seconds: float,
            loop: str) -> tuple[float, float, list]:
    """Drive the window; returns (open, close, generator lateness)."""
    clock, late = ticker.clock, []
    t0 = clock()
    t_end = t0 + seconds
    if loop == "closed":
        for _ in range(traffic.clients()):
            ticker.submit(traffic.next_request(), due=t0)
    arrivals = traffic.arrivals(seconds) if loop == "open" else []
    i, now = 0, t0
    while now < t_end:
        now = clock()
        while i < len(arrivals) and t0 + arrivals[i].due <= now:
            due = t0 + arrivals[i].due
            ticker.submit(arrivals[i], due=due)
            late.append(clock() - due)
            i += 1
        if ticker.e._has_work():
            now, done = ticker.tick()
            if loop == "closed":
                for _ in done:
                    ticker.submit(traffic.next_request(), due=now)
        elif i < len(arrivals):
            time.sleep(max(0.0, min(t0 + arrivals[i].due, t_end) - now))
            now = clock()
        else:
            time.sleep(max(0.0, t_end - now))
            now = clock()
    return t0, now, late


@dataclasses.dataclass
class Facts:
    """What a metric reader may read of one run."""
    conf: dict
    mix: dict
    peaks: dict
    t0: float
    t1: float
    setup_s: float
    stamps: dict
    due: dict  # uid -> host time each request was due
    occupancy: list
    work: Need  # the configuration's counter of needed work
    device: dict | None  # bench/trace.py's reduction; None untraced

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             device: dict, t_start: float, control: str | None = None,
             fault=None) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    conf, mix = cell.conf, cell.mix
    peaks = _json(BENCH / "peaks.json")
    if device["kind"] not in peaks:
        raise SystemExit(f"no peaks for device kind {device['kind']!r} in "
                         "bench/peaks.json")
    setup: dict = {}
    t = time.perf_counter()
    n_slots = mix.get("n_slots", conf["serving"]["n_slots"])
    plain = cell.plain
    cfg = plain.arch_config(conf)
    params = plain.program_params(bmodel.make_weights(plain, conf, seed))
    jax.block_until_ready(params)
    setup["weights_s"], t = time.perf_counter() - t, time.perf_counter()
    engine = bmodel.build_engine(conf, cfg, params, n_slots, mix["max_len"])
    del params
    jax.block_until_ready((engine.params, engine.state))
    setup["engine_s"], t = time.perf_counter() - t, time.perf_counter()
    ticker = Ticker(engine, plain.work_counter(conf))
    if fault is not None:
        fault(engine)
    vocab = cfg.vocab  # the published vocabulary: token ids below it
    traffic = Traffic(mix, seed, vocab, n_slots)
    loop = mix["loop"]
    if loop != "resident":
        warm_up(ticker, vocab, seed)
    setup["warm_s"], t = time.perf_counter() - t, time.perf_counter()
    if loop == "resident":
        for r in traffic.resident():
            ticker.submit(r, due=t)
        while engine.queue or engine.prefilling.any():
            ticker.tick()
    setup["state_s"] = time.perf_counter() - t

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: compiles.append(d) if ev == COMPILE_EVENT
        else None)
    trace_dir = cell.root / TRACE_DIR
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    # set-up's objects are frozen out of the collector, and the collector
    # stays off in the window, so that no full collection stalls a tick
    gc.collect()
    gc.freeze()
    gc.disable()
    setup_s = time.perf_counter() - t_start
    n_compiles = len(compiles)
    ticker.counting, ticker.calls = True, []
    cpu = time.process_time()
    with jax.profiler.TraceAnnotation("bench.window"):
        t0, t1, late = measure(ticker, traffic, seconds, loop)
    cpu = time.process_time() - cpu
    ticker.counting = False
    gc.enable()
    gc.unfreeze()
    in_window = len(compiles) - n_compiles
    reduced = None
    if traced:
        jax.profiler.stop_trace()
        reduced = btrace.reduce(
            btrace.load(str(trace_dir)), calls=ticker.calls,
            step_prefix=ticker.step_name,
            kernel=conf.get("attention_kernel") or "")
        shutil.rmtree(trace_dir, ignore_errors=True)
    log("set-up seconds: " + ", ".join(f"{k} {v:.3f}"
                                       for k, v in setup.items())
        + f"; total {setup_s:.3f} from process start")
    if late:
        log(f"generator lateness: max {max(late) * 1e3:.3f} ms, mean "
            f"{np.mean(late) * 1e3:.3f} ms over {len(late)} arrivals")
    log(f"compiles inside the window: {in_window}")

    facts = Facts(conf=conf, mix=mix,
                  peaks=peaks[device["kind"]],
                  t0=t0, t1=t1, setup_s=setup_s, stamps=ticker.stamps,
                  due=ticker.due,
                  occupancy=ticker.occupancy, work=ticker.work,
                  device=reduced)
    chosen = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for m in chosen:
        value = reader(m["name"], cell.root)(facts)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    done = [u for u, st in ticker.stamps.items()
            if u < WARM_UID and st and t0 < st[-1] <= t1
            and len(st) == ticker.reqs[u].max_new]
    attempted = sum(1 for u, d in ticker.due.items()
                    if u < WARM_UID and d < t1)
    log(f"window {t1 - t0:.3f} s: {attempted} requests attempted, "
        f"{len(done)} completed in it, {len(ticker.calls)} step calls, "
        f"{len(engine.queue)} queued at the close, longest tick "
        f"{ticker.longest_tick * 1e3:.1f} ms, {cpu:.3f} s of process CPU")
    if ticker.slow:
        wall, in_step, slow_cpu = (sum(s[i] for s in ticker.slow)
                                   for i in range(3))
        log(f"ticks over {SLOW_TICK_S * 1e3:.0f} ms: {len(ticker.slow)}, "
            f"{wall:.3f} s in all: {in_step:.3f} s in step calls, "
            f"{slow_cpu:.3f} s of process CPU")

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    out_device = dict(device)
    out_device["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    if reduced is not None:
        out_device["busy_s"] = reduced["busy_s"]
        out_device["window_s"] = reduced["window_s"]

    served = correct.collect(ticker, engine, t0, t1, loop)
    ticker.e = engine = None
    gc.collect()
    compared, program = correct.check(cell, seed, served, control=control)
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in compared.values()),
              "attempted": attempted, "failed": 0, "metrics": metrics,
              "device": out_device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if program is not None:
        result["control"] = {"in_place": control, "program": program}
    result["compared"] = compared
    return result
