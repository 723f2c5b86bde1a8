"""One run of one cell: set up, measure a window, check the answers.

The process that runs this holds the chip. It loads or fits the
configuration's oracle, stands ``LatencyService`` and ``TransportServer``
(through ``BackgroundServer``) up with the configuration's service
settings, and hands the generated requests to a load-generator child
(``loadgen/driver.py``) that imports no JAX. Every end-to-end number is
taken on the child's clock. After the window the served answers are
compared with the plain reference (``reference.py``).
"""
from __future__ import annotations

import gc
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time
from typing import Callable, Optional

import numpy as np

from chipbench import checks, fitcache, spec, spans, tracereduce
from chipbench.reference import Reference
from loadgen import generate as loadgen

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
PATHS = {"predict": "/predict", "advise": "/advise"}


class NoChip(RuntimeError):
    pass


def _log(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


def process_start() -> float:
    """This process's start on the ``time.monotonic`` clock (Linux: from
    ``/proc/self/stat``), so set-up includes the interpreter's start."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        ago = time.clock_gettime(time.CLOCK_BOOTTIME) - started
        return time.monotonic() - ago
    except (OSError, ValueError, IndexError):
        return time.monotonic()


class Ctx:
    """What a metric reader gets: the run's records, counters and trace.
    Times are on the ``time.monotonic`` clock, in seconds."""

    def __init__(self, **kw):
        self.notes = []
        self.__dict__.update(kw)

    def ok(self):
        return [r for r in self.records if r["status"] == 200]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least a share
    ``q`` of the values at or below it."""
    v = sorted(values)
    return float(v[max(0, int(np.ceil(q * len(v))) - 1)])


def _record(endpoint: str, key, due, sent, finish, status, body) -> dict:
    rec = {"key": key, "due": due, "sent": sent, "finish": finish,
           "status": status, "body": None, "service_ms": None}
    if status == 200 and body is not None:
        out = json.loads(body)
        rec["body"] = out["result"] if endpoint == "predict" else out["rows"]
        rec["service_ms"] = out.get("service_ms")
    return rec


def _forest_nodes(params) -> Optional[np.ndarray]:
    return None if "n_nodes" not in params else \
        params["n_nodes"].sum(axis=1)


class Session:
    """Set-up of one cell, once: the device check, the oracle (loaded or
    fitted), the reference, and the service behind a live socket.
    :meth:`window` then measures as many windows as asked.

    A run whose fit cache misses is the first in its checkout since the
    program changed. It also fits each of ``others`` (``(cfg,
    config_bytes)`` pairs; by default every other configuration of the
    benchmark) whose cache misses, and warms its bank up once, so that its
    programs enter the compile cache: every later run of any cell loads
    and warms up from the caches, and only this first run's set-up is a
    cold one (``fit_cache`` in the result line)."""

    def __init__(self, cell_name: str, *, cfg: Optional[dict] = None,
                 config_bytes: Optional[bytes] = None,
                 cache_dir: pathlib.Path = spec.BENCH / ".cache",
                 require_chip: bool = True, jax_cache: bool = True,
                 traffic_overrides: Optional[dict] = None,
                 others: Optional[list] = None,
                 log: Callable[[str], None] = _log):
        self.log = log
        self.bench = spec.benchmark()
        self.cell_name = cell_name
        self.cell = spec.cell(self.bench, cell_name)
        if cfg is None:
            path = spec.ROOT / spec.config_entry(
                self.bench, self.cell["config"])["file"]
            config_bytes = path.read_bytes()
            cfg = json.loads(config_bytes)
        self.cfg = cfg
        self.traffic = dict(spec.traffic(self.cell["traffic"]))
        settings = spec.cell_settings(cell_name)
        self.traffic.update(settings.get("traffic", {}))
        self.traffic.update(traffic_overrides or {})
        self.limits = settings["limits"]
        self.cache_dir = cache_dir

        if jax_cache:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir / "jax")
        # libtpu logs to a fixed system-wide directory unless told otherwise
        os.environ.setdefault("TPU_LOG_DIR", str(cache_dir / "tpu_logs"))
        import jax
        if require_chip and (jax.default_backend() != "tpu"
                             or len(jax.devices()) < self.cell["chips"]):
            raise NoChip(f"JAX found {len(jax.devices())} "
                         f"{jax.default_backend()} device(s); the cell "
                         f"needs {self.cell['chips']} TPU chip(s)")
        self.marks = [("jax", time.monotonic())]
        self.devs = jax.devices()
        d = self.devs[0]
        log(f"device: {d.platform} {d.device_kind} x{len(self.devs)}")
        if jax_cache:
            from repro import compile_cache
            log(f"compile cache: {compile_cache.enable()}")

        self.oracle, paths, hit, fit_s = fitcache.load_or_fit(
            cfg, config_bytes, cache_dir / "fit")
        log(f"fit cache: {'hit' if hit else 'miss'} ("
            + (f"loaded in {fit_s:.3f} s" if hit else
               f"fitted and saved in {fit_s:.3f} s: the first run after a "
               "program change fits, so its set-up is not a steady one")
            + ")")
        self.fit_hit = hit
        self.ref = Reference.load(paths["ref"], paths["data"], cfg)
        self.marks.append(("fit or load", time.monotonic()))
        if not hit:
            self._prepare(self._other_configs() if others is None
                          else others)
            self.marks.append(("other configurations", time.monotonic()))

        from repro.serve import BackgroundServer, LatencyService
        svc = cfg["service"]
        self.service = LatencyService(self.oracle, max_wave=svc["max_wave"],
                                      cache_size=svc["cache_size"])
        bank = self.oracle.bank
        if self.service.stats.degraded or bank is None:
            why = self.service.stats.degraded_reason or self.oracle.bank_error
            raise RuntimeError(f"service degraded at boot: {why}")
        log(f"warm-up {self.service.stats.warmup_ms:.3f} ms, forest backend "
            f"{bank.forest_backend}, {bank.n_groups} groups")
        self.marks.append(("bank and warm-up", time.monotonic()))
        self.bank_pairs = bank.pairs
        self.n_features = bank.n_features
        self.server = BackgroundServer(
            self.service, host="127.0.0.1", port=0,
            max_queue=svc["max_queue"],
            batch_window_s=svc["batch_window_s"]).start()

    def _other_configs(self) -> list:
        out = []
        for entry in self.bench["configs"]:
            if entry["name"] != self.cfg["name"]:
                data = (spec.ROOT / entry["file"]).read_bytes()
                out.append((json.loads(data), data))
        return out

    def _prepare(self, others: list) -> None:
        from repro.serve import LatencyService
        for cfg, config_bytes in others:
            fit_dir = self.cache_dir / "fit"
            if fitcache.cached(fitcache.cache_paths(cfg, config_bytes,
                                                    fit_dir)):
                continue
            t0 = time.monotonic()
            oracle = fitcache.load_or_fit(cfg, config_bytes, fit_dir)[0]
            svc = cfg["service"]
            service = LatencyService(oracle, max_wave=svc["max_wave"],
                                     cache_size=svc["cache_size"])
            if service.stats.degraded:
                raise RuntimeError(f"{cfg['name']} degraded at boot: "
                                   f"{service.stats.degraded_reason}")
            self.log(f"prepared {cfg['name']} for later runs: fitted and "
                     f"warmed up in {time.monotonic() - t0:.3f} s")
            del service, oracle
            gc.collect()

    def window(self, seed: int, seconds: float, trace: bool = False,
               traffic: Optional[dict] = None) -> dict:
        """Generate the traffic from ``seed``, run its warm phase and one
        window through a load-generator child, and return what was
        measured (``out`` from the child, the decoded ``records``, the
        request ``bodies`` by key, compile times, span records, and with
        ``trace`` the trace's extract and reduction)."""
        import jax
        gen = loadgen.generate(traffic or self.traffic, self.ref.data, seed,
                               seconds)
        bodies = loadgen.bodies_in_order(gen)
        self.marks.append(("traffic", time.monotonic()))
        compiles = []

        def on_event(event, duration, **_):
            if event == COMPILE_EVENT:
                compiles.append(time.monotonic())
        jax.monitoring.register_event_duration_secs_listener(on_event)
        records = spans.Records()
        saved = spans.install(records) if trace else None
        bank = self.oracle.bank
        calls0 = (bank.forest_launches, bank.mlp_applies)
        trace_dir = self.cache_dir / "trace"
        child = subprocess.Popen(
            [sys.executable, str(spec.BENCH / "loadgen" / "driver.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        try:
            out = _drive(child, gen, bodies, self.server, trace, trace_dir)
        finally:
            if child.poll() is None:
                child.kill()
            child.wait()
            jax.monitoring.unregister_event_duration_listener(on_event)
            if saved is not None:
                spans.uninstall(saved)
        launched = bank.forest_launches - calls0[0]
        executed = launched or bank.mlp_applies - calls0[1]
        if trace and ((launched and not records.forest)
                      or (executed and not records.bank)):
            raise RuntimeError(
                f"the bank launched the forest {launched} times, but "
                f"chipbench/spans.py recorded {len(records.forest)} "
                f"launches and {len(records.bank)} waves: the calls it "
                "wraps are no longer the ones the bank makes")
        if out["exhausted"]:
            raise RuntimeError(
                f"the closed loop drew all {len(bodies)} requests of its "
                "pool before the window closed: raise max_rate_per_s")
        w0, w1 = out["w0"], out["w1"]
        m = {"gen": gen, "bodies": bodies, "out": out, "w0": w0, "w1": w1,
             "records": [_record(gen["endpoint"], *r)
                         for r in out["records"]],
             "compiles_in_window": sum(w0 <= t <= w1 for t in compiles),
             "compiles": len(compiles), "spans": records}
        if trace:
            m["extract"] = tracereduce.extract(str(trace_dir),
                                               spans.HOST_SPANS)
            m["trace"] = tracereduce.reduce(m["extract"], spans.HOST_SPANS)
        return m

    def memory_peak(self) -> int:
        return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in self.devs[:self.cell["chips"]]))

    def close(self) -> None:
        """Stop the server and drop the program's state."""
        self.server.stop()
        self.service = self.oracle = self.server = None
        gc.collect()

    def check(self, m: dict) -> dict:
        """The numbers ``correct`` compares, for the window ``m``: the
        served answers against the reference."""
        recs, endpoint = m["records"], m["gen"]["endpoint"]
        window_bodies = [m["bodies"][r["key"]] for r in recs]
        expected = (self.ref.predict(window_bodies) if endpoint == "predict"
                    else self.ref.advise(window_bodies))
        return checks.compare(endpoint, [r["body"] for r in recs],
                              [r["status"] for r in recs], expected)


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: Optional[float] = None, log: Callable[[str], None] = _log,
        **session_kw) -> dict:
    """Run ``cell_name`` once and return the result line's object.
    ``session_kw`` go to :class:`Session`: the repository's files by
    default; a test passes its own configuration (and may change traffic
    parameters), with ``require_chip=False`` and no compile cache."""
    t_start = process_start() if t_start is None else t_start
    s = Session(cell_name, log=log, **session_kw)
    try:
        m = s.window(seed, seconds, trace)
        peak = s.memory_peak()
    finally:
        s.close()
    d = s.devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(s.devs), "memory_peak_bytes": peak}
    recs = m["records"]
    marks = [("start", t_start)] + s.marks + [("warm phase", m["w0"])]
    log("set-up: " + ", ".join(f"{b[0]} {b[1] - a[1]:.3f} s"
                               for a, b in zip(marks, marks[1:])))
    log(f"compiles inside the window: {m['compiles_in_window']} "
        f"({m['compiles']} after the warm-up)")
    if m["gen"]["loop"] == "open":
        late = [(r["sent"] - r["due"]) * 1e3 for r in recs
                if r["sent"] is not None]
        log(f"generator lateness: p50 {percentile(late, 0.5):.3f} ms, "
            f"p99 {percentile(late, 0.99):.3f} ms, max {max(late):.3f} ms "
            f"over {len(late)} requests")
    red = m.get("trace")
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]

    ctx = Ctx(endpoint=m["gen"]["endpoint"], loop=m["gen"]["loop"],
              seconds=seconds, w0=m["w0"], w1=m["w1"],
              wait_until=m["w1"] + m["gen"]["drain_s"], records=recs,
              statsz_before=m["out"]["statsz_before"],
              statsz_after=m["out"]["statsz_after"],
              setup_s=m["w0"] - t_start, trace=red,
              extract=m.get("extract"),
              forest_launches=[r for r in m["spans"].forest
                               if m["w0"] <= r[0] and r[1] <= m["w1"]],
              bank_waves=[r for r in m["spans"].bank
                          if m["w0"] <= r[0] and r[1] <= m["w1"]],
              peaks=spec.peaks(d.device_kind) if trace else None,
              n_features=s.n_features, bank_pairs=s.bank_pairs, cfg=s.cfg,
              forest_nodes=_forest_nodes(s.ref.p),
              ref_pairs=[tuple(p) for p in s.ref.data["pairs"]])
    metrics = {}
    for metric in (spec.per_layer(s.bench, cell_name) if trace
                   else spec.end_to_end(s.bench, cell_name)):
        value = spec.reader(metric["name"])(ctx)
        if value is not None:
            metrics[metric["name"]] = {"value": value,
                                       "unit": metric["unit"]}
    for note in ctx.notes:
        log(note)

    t0 = time.monotonic()
    numbers = s.check(m)
    log(f"reference: {len(recs)} requests checked in "
        f"{time.monotonic() - t0:.3f} s")
    result = {"correct": checks.verdict(numbers, s.limits),
              "attempted": len(recs),
              "failed": sum(r["status"] != 200 for r in recs),
              "metrics": metrics, "device": device,
              "fit_cache": "hit" if s.fit_hit else "miss"}
    if red is not None:
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = {k: {"value": numbers[k], "limit": s.limits[k]}
                        for k in checks.NUMBERS}
    for line in checks.lines(numbers, s.limits):
        log(line)
    return result


def _drive(child, gen: dict, bodies, bg, trace: bool,
           trace_dir: pathlib.Path) -> dict:
    """Hand the child its requests, hold the window (tracing it when
    asked) and return what the child measured."""
    import jax
    path = PATHS[gen["endpoint"]]
    child_spec = {"host": bg.host, "port": bg.port, "loop": gen["loop"],
                  "warm_s": gen["warm_s"], "seconds": gen["seconds"],
                  "drain_s": gen["drain_s"],
                  "requests": [[path, json.dumps(b)] for b in bodies]}
    if gen["loop"] == "open":
        child_spec.update(warm_due=gen["warm_due"],
                          window_due=gen["window_due"],
                          connections=gen["connections"])
    else:
        child_spec["clients"] = gen["clients"]
    child.stdin.write(json.dumps(child_spec) + "\n")
    child.stdin.flush()
    _expect(child, "READY")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(tracereduce.WINDOW):
                child.stdin.write("GO\n")
                child.stdin.flush()
                _expect(child, "DONE")
        finally:
            jax.profiler.stop_trace()
    else:
        child.stdin.write("GO\n")
        child.stdin.flush()
        _expect(child, "DONE")
    line = child.stdout.readline()
    if child.wait(timeout=120) != 0 or not line:
        raise RuntimeError(f"load generator failed (exit {child.returncode})")
    return json.loads(line)


def _expect(child, word: str) -> None:
    line = child.stdout.readline().strip()
    if line != word:
        raise RuntimeError(f"load generator said {line!r}, not {word!r} "
                           f"(exit {child.poll()})")
