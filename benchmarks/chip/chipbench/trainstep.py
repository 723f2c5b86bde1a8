"""One run of a training-step cell: set up, time a window of steps, check
the first steps against the plain reference.

Everything this knows of an architecture comes from one module, the file
that the configuration's ``"reference"`` names (:func:`reference`; the
dense decoder's is ``chipbench/lmref.py``). It provides:

- ``dims(cfg)``: the reference's sizes, a ``vocab`` among them (the
  vocabulary the rows are drawn from);
- ``program_config(cfg)``: the program's ``ModelConfig``, the file's cut
  applied and checked against every width the file gives;
- ``PROGRAM_PATHS``: the program's parameter tree by the reference's leaf
  names, and ``VOCAB_AXES``: the leaves whose vocabulary axis the program
  pads, by that axis;
- ``init_weights(key, dims)``, ``readings(key, batches, dims, adam,
  numerics)``, ``leaf_names(dims)``, ``leaf_norms(tree)``, ``Adam``,
  ``REFERENCE`` and ``CONTROL``: the reference itself;
- ``step_flops(dims, batch, seq)``: the model FLOPs of one step, the
  yardstick of ``mfu.train``.

The program's own entry points build the job: ``repro.configs`` gives the
model (as the module's ``program_config`` cuts it), ``launch.steps`` the
train step and the optimizer's settings, ``train.optimizer`` the state.
The benchmark makes the weights (the module's ``init_weights``, one jitted
call from ``--seed``) and the token rows (uniform over the vocabulary,
drawn on the device from ``--seed``), and hands both to the program.

Set-up builds the compiled step with its state and drives it through its
first ``CHECKED_STEPS`` steps, on rows that all differ, through the same
call and feed as the window. It reads the three numbers the comparison
takes from the program's own state: each step's loss, the first step's
clipped gradient per leaf (from Adam's first moment, ``m / (1 - b1)``)
and the parameters' change per leaf after the last of them. The window
then runs further steps back to back, each waited on for its loss, the
host queueing the next step before it waits, as a training loop does.
Once it has closed, the program's state is freed and the reference trains
the same weights on the same rows in float32.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import os
import pathlib
import shutil
import sys
import time
from typing import Callable, Dict, Optional

import numpy as np

from chipbench import checks, harness, spec, tracereduce

# two, not three: at the cell's size the reference's third step would
# take it past the window's length
CHECKED_STEPS = 2
# what an architecture's module provides (the module docstring says what)
INTERFACE = ("dims", "program_config", "PROGRAM_PATHS", "VOCAB_AXES",
             "init_weights", "readings", "leaf_names", "leaf_norms", "Adam",
             "REFERENCE", "CONTROL", "step_flops")
HOST_SPANS = ("chipbench.feed", "chipbench.step", "chipbench.wait")
NUMBERS = ("loss_gap", "grad_gap", "change_gap")
# a leaf whose reference gradient is below this share of the median
# leaf's moves under Adam by round-off alone: its change is not compared
STILL_LEAF = 1e-3


def reference(cfg: dict, where: str):
    """The module that a training configuration's ``"reference"`` names
    (a path under ``benchmarks/chip``), loaded once per process; ``where``
    names the configuration's file in the error where the key, the file or
    a name of :data:`INTERFACE` is missing."""
    rel = cfg.get("reference")
    if not rel:
        raise KeyError(f"{where}: a training configuration names its "
                       "reference module under \"reference\"")
    path = (spec.BENCH / rel).resolve()
    if not path.is_file():
        raise FileNotFoundError(f"{where}: its reference {rel!r} is no file "
                                f"under {spec.BENCH}")
    name = f"chipbench_train_reference:{path}"
    if name not in sys.modules:
        loader = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(loader)
        # a module's dataclasses look their module up while it loads
        sys.modules[name] = mod
        try:
            loader.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    ref = sys.modules[name]
    missing = [n for n in INTERFACE if not hasattr(ref, n)]
    if missing:
        raise AttributeError(f"{where}: its reference {rel} lacks "
                             f"{', '.join(missing)}")
    return ref


def adam(cfg: dict, ref):
    """The job's optimizer settings as the reference module's ``Adam``."""
    o = cfg["job"]["optimizer"]
    return ref.Adam(**{f.name: (tuple(o[f.name]) if f.name == "decay"
                                else o[f.name])
                       for f in dataclasses.fields(ref.Adam)})


def seed_key(seed: int, purpose: int):
    """A JAX key for ``purpose`` (0 weights, 1 rows) from any whole
    ``seed``, however large."""
    import jax
    state = np.random.SeedSequence([int(seed) % 2**64, purpose]
                                   ).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(state, impl="threefry2x32")


def footprint(compiled) -> dict:
    """The compiler's account of what ``compiled`` holds on the device:
    its arguments, outputs (less those written over donated arguments),
    temporaries and code, and their sum; empty where the backend gives
    none."""
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    out = {"arguments": ma.argument_size_in_bytes,
           "outputs": ma.output_size_in_bytes - ma.alias_size_in_bytes,
           "temporaries": ma.temp_size_in_bytes,
           "code": ma.generated_code_size_in_bytes}
    out["total"] = sum(out.values())
    return out


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def to_program(weights: dict, abstract, ref) -> dict:
    """The reference's weights in the program's tree, by the reference
    module ``ref``'s map, each leaf's rows or columns past the vocabulary
    (the program pads them) zero."""
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(abstract)[0]
    by_path = {tuple(k.key for k in p): s for p, s in flat}
    if set(by_path) != set(ref.PROGRAM_PATHS.values()):
        raise RuntimeError(f"the program's parameters are {sorted(by_path)}, "
                           f"not those {ref.__file__} maps: "
                           f"{sorted(ref.PROGRAM_PATHS.values())}")
    out: dict = {}
    for name, path in ref.PROGRAM_PATHS.items():
        want, x = by_path[path], weights[name]
        if name in ref.VOCAB_AXES:
            pad = [(0, 0)] * x.ndim
            axis = ref.VOCAB_AXES[name]
            pad[axis] = (0, want.shape[axis] - x.shape[axis])
            x = jnp.pad(x, tuple(pad))
        if x.shape != want.shape:
            raise RuntimeError(f"{'/'.join(path)}: the program holds "
                               f"{want.shape}, the reference {x.shape}")
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = x.astype(want.dtype)
    return out


def from_program(tree, vocab: int, ref) -> dict:
    """The program's tree by the reference's leaf names, the padded rows or
    columns dropped."""
    out = {n: _get(tree, p) for n, p in ref.PROGRAM_PATHS.items()}
    for name, axis in ref.VOCAB_AXES.items():
        out[name] = out[name][(slice(None),) * axis + (slice(0, vocab),)]
    return out


def compare(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
            names) -> Dict[str, object]:
    """The numbers ``correct`` compares, and the leaf behind each:

    - ``loss_gap``: the largest ``|loss - reference|`` over the reference,
      over the checked steps;
    - ``grad_gap``: over the leaves, the largest gap between the norm of
      the program's first clipped gradient and the reference's, over the
      larger of the reference's norm of that leaf and of the median leaf;
    - ``change_gap``: the same of the parameters' change after the checked
      steps, over the leaves whose reference gradient is at least
      ``STILL_LEAF`` of the median leaf's.
    """
    def worst(a, b, keep):
        a, b = np.asarray(a, np.float64)[keep], np.asarray(b, np.float64)[keep]
        g = np.abs(a - b) / np.maximum(b, np.median(b))
        g = np.where(np.isfinite(g), g, np.inf)
        i = int(np.argmax(g))
        return float(g[i]), np.asarray(names)[keep][i]
    lr = np.asarray(want["loss"], np.float64)
    lg = np.abs(np.asarray(got["loss"], np.float64) - lr) / np.abs(lr)
    lg = np.where(np.isfinite(lg), lg, np.inf)
    every = np.ones(len(names), bool)
    moving = want["grad"] >= STILL_LEAF * np.median(want["grad"])
    grad_gap, grad_at = worst(got["grad"], want["grad"], every)
    change_gap, change_at = worst(got["change"], want["change"], moving)
    return {"loss_gap": float(lg.max()), "grad_gap": grad_gap,
            "change_gap": change_gap, "loss_at": int(np.argmax(lg)) + 1,
            "grad_at": grad_at, "change_at": change_at,
            "leaves_compared": int(moving.sum()), "leaves": len(names)}


class TrainSession:
    """Set-up of one training cell: the device check and the program's
    step; :meth:`start` then makes the weights, state and rows of a seed
    and drives the step through the checked steps, and :meth:`window`
    times further steps."""

    def __init__(self, cell_name: str, *,
                 cfg: Optional[dict] = None,
                 cache_dir: pathlib.Path = spec.BENCH / ".cache",
                 require_chip: bool = True, jax_cache: bool = True,
                 traffic_overrides: Optional[dict] = None,
                 log: Callable[[str], None] = harness._log):
        self.log = log
        self.bench = spec.benchmark()
        self.cell_name = cell_name
        self.cell = spec.cell(self.bench, cell_name)
        where = spec.config_entry(self.bench, self.cell["config"])["file"]
        if cfg is None:
            cfg = spec.load_json(spec.ROOT / where)
        self.cfg = cfg
        # the architecture's module: the reference and all else the
        # harness knows of the architecture
        self.ref = ref = reference(cfg, where)
        self.traffic = dict(spec.traffic(self.cell["traffic"]))
        settings = spec.cell_settings(cell_name)
        self.traffic.update(settings.get("traffic", {}))
        self.traffic.update(traffic_overrides or {})
        self.limits = settings["limits"]
        self.cache_dir = cache_dir
        if jax_cache:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir / "jax")
        os.environ.setdefault("TPU_LOG_DIR", str(cache_dir / "tpu_logs"))
        import jax
        if require_chip and (jax.default_backend() != "tpu"
                             or len(jax.devices()) < self.cell["chips"]):
            raise harness.NoChip(
                f"JAX found {len(jax.devices())} {jax.default_backend()} "
                f"device(s); the cell needs {self.cell['chips']} TPU chip(s)")
        self.marks = [("jax", time.monotonic())]
        self.devs = jax.devices()
        d = self.devs[0]
        log(f"device: {d.platform} {d.device_kind} x{len(self.devs)}")
        if jax_cache:
            from repro import compile_cache
            log(f"compile cache: {compile_cache.enable()}")
        self.compiles = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

        from repro.launch import steps as ST
        from repro.models import model as M
        from repro.train import optimizer as OPT
        self.dims, self.adam = ref.dims(cfg), adam(cfg, ref)
        mc = ref.program_config(cfg)
        o = cfg["job"]["optimizer"]
        hp = ST.make_opt_hparams(mc, **{
            k: o[k] for k in ("learning_rate", "b1", "b2", "eps",
                              "weight_decay", "grad_clip", "warmup_steps",
                              "decay_steps", "min_lr_ratio")})
        if hp.state_dtype != cfg["job"]["state_dtype"]:
            raise RuntimeError(f"the program keeps Adam's moments in "
                               f"{hp.state_dtype}, the job states "
                               f"{cfg['job']['state_dtype']}")
        self.b1 = hp.b1
        abstract = M.abstract_init(mc)[0]
        dm, B, S = self.dims, int(self.traffic["batch"]), \
            int(self.traffic["seq_len"])
        self.batch, self.seq = B, S

        def init(key):
            params = to_program(ref.init_weights(key, dm), abstract, ref)
            return params, OPT.init_state(params, hp)

        def feed(key, i):
            t = jax.random.randint(jax.random.fold_in(key, i), (B, S + 1),
                                   0, dm.vocab, dtype=jax.numpy.int32)
            return {"tokens": t[:, :-1], "labels": t[:, 1:]}

        def change(params, key):
            p0 = ref.init_weights(key, dm)
            now = from_program(params, dm.vocab, ref)
            return ref.leaf_norms({k: now[k] - p0[k] for k in now})

        self._init = jax.jit(init)
        self.feed = jax.jit(feed)
        # the one compiled step every call runs, its state donated
        state = jax.eval_shape(self._init, seed_key(0, 0))
        rows = jax.eval_shape(self.feed, seed_key(0, 1), 1)
        self.step = jax.jit(ST.make_train_step(mc, hp),
                            donate_argnums=(0, 1)).lower(*state, rows).compile()
        self.marks.append(("step compiled", time.monotonic()))
        self.footprint = footprint(self.step)
        if self.footprint:
            log("compiled step: " + ", ".join(
                f"{k} {v} bytes" for k, v in self.footprint.items()))
        self._grad = jax.jit(lambda m: ref.leaf_norms(
            from_program(m, dm.vocab, ref)) / (1.0 - hp.b1))
        self._change = jax.jit(change)
        self.flops_per_step = ref.step_flops(dm, B, S)

    def start(self, seed: int) -> None:
        """Weights, state and rows from ``seed``; the checked steps, and
        the program's readings of them."""
        self.wkey, self.dkey = seed_key(seed, 0), seed_key(seed, 1)
        self.params, self.opt = self._init(self.wkey)
        import jax
        jax.block_until_ready((self.params, self.opt))
        self.marks.append(("weights and state", time.monotonic()))
        losses = []
        for i in range(1, CHECKED_STEPS + 1):
            losses.append(float(self._one(i)))
            self.marks.append((f"step {i}", time.monotonic()))
            if i == 1:
                grad = np.asarray(self._grad(self.opt["m"]))
        self.got = {"loss": np.asarray(losses), "grad": grad,
                    "change": np.asarray(self._change(self.params,
                                                      self.wkey))}
        self.next_step = CHECKED_STEPS + 1
        self.log("checked steps' losses: "
                 + ", ".join(f"{x:.6f}" for x in losses))

    def _on_event(self, event, duration, **_):
        if event == harness.COMPILE_EVENT:
            self.compiles.append(time.monotonic())

    def _dispatch(self, i: int):
        """Queue step ``i``, its rows drawn on the device, and return its
        loss, not yet waited on."""
        import jax
        with jax.profiler.TraceAnnotation("chipbench.feed"):
            b = self.feed(self.dkey, i)
        with jax.profiler.TraceAnnotation("chipbench.step"):
            self.params, self.opt, out = self.step(self.params, self.opt, b)
        return out["loss"]

    def _one(self, i: int):
        import jax
        loss = self._dispatch(i)
        with jax.profiler.TraceAnnotation("chipbench.wait"):
            return loss.block_until_ready()

    def window(self, seconds: float, trace: bool = False) -> dict:
        """Steps back to back until ``seconds`` have passed, each waited on
        for its loss once the next is queued (no step is queued after the
        ``seconds``); with ``trace`` under the profiler."""
        import jax
        trace_dir = self.cache_dir / "trace"
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        losses, ends = [], []
        try:
            with harness.quiet_collector() as pauses, \
                    jax.profiler.TraceAnnotation(tracereduce.WINDOW):
                w0 = time.monotonic()
                loss = self._dispatch(self.next_step)
                while loss is not None:
                    # the next step is queued before this one is waited on
                    self.next_step += 1
                    ahead = (self._dispatch(self.next_step)
                             if time.monotonic() - w0 < seconds else None)
                    with jax.profiler.TraceAnnotation("chipbench.wait"):
                        losses.append(float(loss.block_until_ready()))
                    ends.append(time.monotonic())
                    loss = ahead
        finally:
            if trace:
                jax.profiler.stop_trace()
        m = {"w0": w0, "w1": ends[-1], "steps": len(ends), "ends": ends,
             "losses": losses, "pauses": pauses,
             "compiles_in_window": sum(w0 <= t <= ends[-1]
                                       for t in self.compiles)}
        if trace:
            m["extract"] = tracereduce.extract(str(trace_dir), HOST_SPANS)
            m["trace"] = tracereduce.reduce(m["extract"], HOST_SPANS)
        return m

    def memory_peak(self) -> int:
        return harness.memory_peak(self.devs[:self.cell["chips"]])

    def free(self) -> None:
        """Drop the program's weights and state, so the reference has the
        chip's memory."""
        self.params = self.opt = None
        gc.collect()

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        self.free()

    def batches(self) -> list:
        """The checked steps' ``(tokens, labels)``, drawn again."""
        return [(b["tokens"], b["labels"]) for b in
                (self.feed(self.dkey, i)
                 for i in range(1, CHECKED_STEPS + 1))]

    def check(self) -> dict:
        """The reference trained on the same weights and rows; the numbers
        against the program's readings."""
        want = self.ref.readings(self.wkey, self.batches(), self.dims,
                                 self.adam, self.ref.REFERENCE)
        return compare(self.got, want, self.ref.leaf_names(self.dims))


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, log: Callable[[str], None] = harness._log,
        **session_kw) -> dict:
    s = TrainSession(cell_name, log=log, **session_kw)
    try:
        s.start(seed)
        m = s.window(seconds, trace)
        peak = s.memory_peak()
    finally:
        s.close()
    d = s.devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(s.devs), "memory_peak_bytes": peak}
    marks = [("start", t_start)] + s.marks + [("window", m["w0"])]
    log("set-up: " + ", ".join(f"{b[0]} {b[1] - a[1]:.3f} s"
                               for a, b in zip(marks, marks[1:])))
    log(f"compiles inside the window: {m['compiles_in_window']}")
    st = s.devs[0].memory_stats() or {}
    log(f"memory peak: {peak} bytes (buffers "
        f"{st.get('peak_bytes_in_use')}, reserved for temporaries "
        f"{st.get('peak_bytes_reserved')}, of {st.get('bytes_limit')})")
    times = np.diff([m["w0"]] + m["ends"]) * 1e3
    log(f"{m['steps']} steps in {m['w1'] - m['w0']:.3f} s; step times (ms): "
        + " ".join(f"{t:.3f}" for t in times[:40])
        + (f" ... ({len(times) - 40} more)" if len(times) > 40 else ""))
    log(harness.pauses_line(m["pauses"], m["w0"], m["w1"]))
    if m["compiles_in_window"]:
        raise RuntimeError(f"{m['compiles_in_window']} compiles inside the "
                           "window: set-up did not warm every program up")
    red = m.get("trace")
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    ctx = harness.Ctx(seconds=seconds, w0=m["w0"], w1=m["w1"],
                      steps=m["steps"], setup_s=m["w0"] - t_start, trace=red,
                      extract=m.get("extract"),
                      flops_per_step=s.flops_per_step,
                      peaks=spec.peaks(d.device_kind) if trace else None)
    metrics = {}
    for metric in (spec.per_layer(s.bench, cell_name) if trace
                   else spec.end_to_end(s.bench, cell_name)):
        value = spec.reader(metric["name"])(ctx)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    t0 = time.monotonic()
    numbers = s.check()
    log(f"reference: {CHECKED_STEPS} steps in {time.monotonic() - t0:.3f} s; "
        f"worst leaves: gradient {numbers['grad_at']}, change "
        f"{numbers['change_at']} ({numbers['leaves_compared']} of "
        f"{numbers['leaves']} compared), loss at step {numbers['loss_at']}")
    failed = sum(not np.isfinite(x) for x in m["losses"])
    result = {"correct": checks.verdict(numbers, s.limits)
              and not failed,
              "attempted": m["steps"], "failed": failed,
              "metrics": metrics, "device": device}
    if red is not None:
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = {k: {"value": numbers[k], "limit": s.limits[k]}
                        for k in NUMBERS}
    for line in checks.lines(numbers, s.limits):
        log(line)
    return result
