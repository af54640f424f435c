"""One run of one benchmark cell: set-up, the measured window, the check.

A cell is a workload of ``BENCHMARK.json``: a configuration
(``configs/<config>.json``, whose ``arch`` names its plain reference in
``references/<arch>.py``) under a traffic mix (``traffic/<traffic>.json``).
The harness finds all three, and each per-layer metric's reader
(``metrics/<name>.py``), by name.

What a run does, in order:

1. Make the click-log pool from the seed (``pool.py``).
2. Warm up: one short ``Emulator.run`` through the same probes, whose span
   holds a save of each kind and a failure.  It compiles every program the
   window runs, and its timings size the window.
3. The window run: ``Emulator.run(max_steps=N)`` with a ``CPRManager``
   (in-memory store) and a pinned ``FailureInjector``.  Its steps 0-2 are
   set-up: the check reads their losses, the first gradient (from the
   optimizer's state) and the weights after them.  The window opens at the
   entry of step 3, once the device has finished step 2, and closes when the
   emulator asks for its first evaluation batch, once the device has
   finished the last step.  Every save and every failure falls inside it.
4. After the window: peak device memory, then the program's state is freed
   and the plain reference decides ``correct``.

Probes sit on the program from outside: an ``Emulator`` subclass wraps the
jitted step, and wrappers sit on the manager's ``run_save``,
``on_failure`` and ``attach_store`` and on the injector's ``between``.  Each
event clock starts after the device has drained, and each probe opens a
``jax.profiler.TraceAnnotation`` (``bench.step``, ``bench.save``,
``bench.failure``, ``bench.batch``, ``bench.eval``, ``bench.window``).
Under full recovery the probes also read, after each save's clock, the
store's image at rows drawn from the seed against the device state the save
was given, and after each restore's clock the device state handed back
against the one the failure found (``checks.py``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import checks  # noqa: E402
import pool as pool_mod  # noqa: E402

WINDOW_STEP = 3          # steps 0..2 are read by the check, before the window
SPAN_SLACK = 1e-9        # the last save lands at the span's end, not past it
IMAGE_SAMPLES = 4096     # pool samples whose rows each full save is read at
# the check's control: the reference one precision below the configuration's
CONTROL_PRECISION = {"highest": "bf16_3x"}
# keys of a configuration file that the harness reads, not the model; every
# other key names a field of the program's ``DLRMConfig``
RUN_KEYS = ("arch", "batch_size", "param_dtype", "matmul_precision",
            "optimizer", "reduced", "assumed", "deployment")


# ------------------------------------------------------------ discovery ----
def _json(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root=ROOT):
    return _json(os.path.join(root, "BENCHMARK.json"))


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def load_config(bench, name, root=ROOT):
    return _json(os.path.join(root, find(bench["configs"], name,
                                         "configuration")["file"]))


def load_traffic(name):
    return _json(os.path.join(HERE, "traffic", name + ".json"))


def load_limits():
    return _json(os.path.join(HERE, "limits.json"))["limits"]


def _module(path, name):
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _frozen(v):
    return tuple(_frozen(x) for x in v) if isinstance(v, list) else v


def program_config(cfg):
    """The program's ``DLRMConfig`` from every model key of a configuration
    file; a key that is neither a field of it nor a run key is refused."""
    from repro.models.dlrm import DLRMConfig
    fields = {f.name for f in dataclasses.fields(DLRMConfig)}
    unknown = sorted(set(cfg) - fields - set(RUN_KEYS))
    if unknown:
        raise ValueError(f"configuration {cfg.get('name')!r}: key(s) "
                         f"{', '.join(unknown)} name no field of the "
                         f"program's DLRMConfig and no run key {RUN_KEYS}")
    return DLRMConfig(**{k: _frozen(v) for k, v in cfg.items()
                         if k in fields})


def metric_reader(name):
    return _module(os.path.join(HERE, "metrics", name + ".py"),
                   "bench_metric_" + name.replace(".", "_").replace("-", "_"))


def reference(arch):
    return _module(os.path.join(HERE, "references", arch + ".py"),
                   "bench_reference_" + arch)


def metrics_for(bench, workload, kind):
    """The ``end_to_end`` or ``per_layer`` entries this workload reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


# ------------------------------------------------------------- compiles ----
class CompileCounter:
    """Programs built (a persistent-cache hit included), compile requests
    and cache hits, from JAX's monitoring events; installed once per
    process."""
    _installed = None

    def __init__(self):
        self.compiles = 0
        self.requests = 0
        self.hits = 0

    @classmethod
    def get(cls):
        if cls._installed is None:
            c = cls()
            jax.monitoring.register_event_duration_secs_listener(c._on_dur)
            jax.monitoring.register_event_listener(c._on_event)
            cls._installed = c
        return cls._installed

    def _on_dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        """(programs built, compile requests, persistent-cache hits)."""
        return self.compiles, self.requests, self.hits


def enable_compile_cache(root=ROOT):
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<checkout>/.jax_cache``; every program is kept, however quick."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# --------------------------------------------------------------- probes ----
class _Span:
    """A TraceAnnotation opened and closed by hand."""

    def __init__(self, name):
        self.ann = jax.profiler.TraceAnnotation(name)
        self.ann.__enter__()

    def close(self):
        self.ann.__exit__(None, None, None)


class StopRun(Exception):
    """Raised by the step probe to end a run before its window."""


class Probes:
    """Clocks, counters and the check's readings of one ``Emulator.run``."""

    def __init__(self, arch, ref, seed, first_rows, faults=(), record=False,
                 trace_dir=None, image_rows=None):
        self.jax = jax
        self.arch, self.ref, self.faults = arch, ref, set(faults)
        self.first_rows = [jnp.asarray(r, jnp.int32) for r in first_rows]
        self.seed, self.key = seed, jax.random.PRNGKey(seed)
        self.record, self.trace_dir = record, trace_dir
        self.i = 0                       # step calls so far
        self.latest = None               # outputs of the newest step
        self.t_start = self.t_end = None
        self.window_steps = 0
        self.saves, self.failures = [], []   # dicts, in order
        self.saved_rows = []             # (save index, table, rows)
        self.failure_states = []         # what each restore saw and returned
        self.check = {"loss": []}
        self.pending = None              # (t0, span) of a failure in flight
        self.compiles = CompileCounter.get()
        self.c0 = self.c1 = None
        self._win_span = None
        self.mgr = None
        self.stop_at_window = False
        self.run_saves = None            # save indices to run (None: all)
        self.memory = []                 # (where, bytes in use, peak)
        # full recovery: rows of each table whose saved image every save's
        # check reads (None: another mode), and the device state's digest at
        # the newest save, which the whole image is held to after the window
        self.image_rows = image_rows
        self.image_rows_dev = None if image_rows is None else [
            jnp.asarray(r, jnp.int32) for r in image_rows]
        self.image_digest = None

    # -- helpers --
    def _note_memory(self, where):
        stats = self.jax.devices()[0].memory_stats() or {}
        self.memory.append((where, stats.get("bytes_in_use"),
                            stats.get("peak_bytes_in_use")))

    def in_window(self):
        return self.t_start is not None and self.t_end is None

    def drain(self):
        if self.latest is not None:
            self.jax.block_until_ready(self.latest)

    def _end_failure(self):
        t0, span, rec = self.pending
        self.pending = None
        rec["s"] = time.perf_counter() - t0
        span.close()
        self._note_memory(f"failure {len(self.failures) - 1}")

    # -- the window --
    def _open_window(self):
        self.drain()
        self.jax.block_until_ready(self.check)
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.jax.profiler.start_trace(self.trace_dir,
                                          profiler_options=opts)
        self._note_memory("window")
        self._win_span = _Span("bench.window")
        self.c0 = self.compiles.snapshot()
        self.bytes0 = self.mgr.store.bytes_written
        self.t_start = time.perf_counter()

    def on_eval(self):
        self.drain()
        if self.pending is not None:
            self._end_failure()
        self.t_end = time.perf_counter()
        self.c1 = self.compiles.snapshot()
        self.bytes1 = self.mgr.store.bytes_written
        self._win_span.close()
        if self.trace_dir:
            self.jax.profiler.stop_trace()

    # -- the step --
    def wrap_step(self, step):
        jax = self.jax

        def probed(params, ostate, tracker, batch):
            i = self.i
            if self.pending is not None:
                jax.block_until_ready((params, ostate))
                rec = self.pending[2]
                self._end_failure()
                if self.image_rows is not None:
                    # full recovery replays from the last full save, so it
                    # hands back the state the failure found, row for row
                    rec["rows_changed"] = int(rows_changed(
                        (self.latest[0]["tables"],
                         self.latest[1]["acc"]["tables"]),
                        (params["tables"], ostate["acc"]["tables"])))
            if i == WINDOW_STEP:
                if self.stop_at_window:
                    raise StopRun()
                self._open_window()
            # after a restore the newest outputs hold the tables replaced;
            # let them go before the step allocates its own
            self.latest = None
            if "half_batch" in self.faults:
                half = len(batch["label"]) // 2
                batch = {k: v[:half] for k, v in batch.items()}
            with jax.profiler.TraceAnnotation("bench.step"):
                out = step(params, ostate, tracker, batch)
            if "stale_state" in self.faults:
                out = (params, ostate) + tuple(out[2:])
            self.latest = out
            self.i += 1
            if self.in_window():
                self.window_steps += 1
            if i < WINDOW_STEP:
                self._read_check(i, out)
            return out

        return probed

    def _read_check(self, i, out):
        params, ostate, _, loss = out
        self.check["loss"].append(loss)
        if i == 0:
            self.check["grad"] = acc_grad_norms(ostate["acc"], params)
            self.check["row_norms"] = self.ref.row_grad_norms(
                ostate["acc"], params, self.first_rows)
        if i == WINDOW_STEP - 1:
            self.check["change"] = self.ref.change_norms(self.arch, self.key,
                                                         params)

    # -- the manager and the injector --
    def wrap_manager(self, mgr, injector):
        jax = self.jax
        self.mgr = mgr
        run_save, on_failure = mgr.run_save, mgr.on_failure
        attach_store, between = mgr.attach_store, injector.between

        def probed_save(t_event, tables, accs, tracker_state,
                        trainer_state=None, step=0, pending_indices=None):
            if self.run_saves is not None and \
                    not self.run_saves(len(self.saves), t_event):
                self.saves.append({"step": step, "window": False,
                                   "skipped": True})
                return tracker_state
            self.drain()
            rec = {"step": step, "window": self.in_window()}
            self.saves.append(rec)
            b0 = mgr.store.bytes_written
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.save"):
                out = run_save(t_event, tables, accs, tracker_state,
                               trainer_state=trainer_state, step=step,
                               pending_indices=pending_indices)
                jax.block_until_ready(out)
            rec["s"] = time.perf_counter() - t0
            rec["bytes"] = mgr.store.bytes_written - b0
            self._note_memory(f"save {len(self.saves) - 1}")
            if self.image_rows is not None:
                self._check_image(rec, tables, accs, trainer_state)
            return out

        def probed_between(t0, t1):
            events = between(t0, t1)
            if events:
                self.drain()
                rec = {"step": self.i - 1, "window": self.in_window(),
                       "shards": [tuple(e.shard_ids) for e in events]}
                self.failures.append(rec)
                self.pending = (time.perf_counter(), _Span("bench.failure"),
                                rec)
            return events

        def probed_failure(event, tables, accs):
            new_t, new_a, info = on_failure(event, tables, accs)
            if "alter_restore" in self.faults:
                t = int(np.argmax([len(x) for x in new_t]))
                j = min(set(range(mgr.p.N_emb)) - set(event.shard_ids))
                lo, _ = self.ref.shard_range(len(new_t[t]), j, mgr.p.N_emb)
                new_t = list(new_t)
                new_t[t] = np.array(new_t[t])
                new_t[t][lo] += 1.0
            if self.record and self.image_rows is None:
                self.failure_states.append(
                    {"save_index": len(self.saves), "shards": event.shard_ids,
                     "old": (tables, accs), "new": (new_t, new_a)})
            return new_t, new_a, info

        def probed_attach(*a, **kw):
            attach_store(*a, **kw)
            if self.record:
                store_rows = mgr.store.save_rows

                def rows_probe(table, rows, values, acc_values, step=0):
                    if "alter_selection" in self.faults and len(rows):
                        rows = np.array(rows)
                        rows[0] = (rows[0] + 1) % mgr.table_sizes[table]
                    self.saved_rows.append((len(self.saves) - 1, table,
                                            np.asarray(rows)))
                    return store_rows(table, rows, values, acc_values,
                                      step=step)

                mgr.store.save_rows = rows_probe

        mgr.run_save = probed_save
        mgr.on_failure = probed_failure
        mgr.attach_store = probed_attach
        injector.between = probed_between

    def _check_image(self, rec, tables, accs, trainer_state):
        """What a full save stored against the device state it was given:
        the sampled rows of every table and accumulator and the whole
        trainer replica now; a digest of the whole state for the check
        after the window."""
        store = self.mgr.store
        if "alter_image" in self.faults:
            t = int(np.argmax([len(x) for x in store.image_tables]))
            rng = np.random.default_rng([self.seed, 0x1A6E, len(self.saves)])
            store.image_tables[t][rng.integers(len(store.image_tables[t]))] \
                += 1.0
        got_t, got_a = jax.device_get(take_rows(tables, accs,
                                                self.image_rows_dev))
        bad = sum(checks.rows_differ(gt, it[r]) + checks.rows_differ(ga, ia[r])
                  for gt, ga, it, ia, r in zip(got_t, got_a, store.image_tables,
                                               store.image_accs,
                                               self.image_rows))
        bad += sum(not np.array_equal(np.asarray(a), b) for a, b in zip(
            jax.tree.leaves(trainer_state),
            jax.tree.leaves(store.trainer_image)))
        rec["image_rows_differ"] = bad
        self.image_digest = state_digest(list(tables) + list(accs))

    def image_arrays_differ(self, store):
        """Tables and accumulators of the store's image whose digest differs
        from the device state's at the newest save (None: no save)."""
        if self.image_digest is None:
            return None
        image = list(store.image_tables) + list(store.image_accs)
        return int(np.count_nonzero(np.asarray(self.image_digest) !=
                                    np.asarray(state_digest(image))))

    # -- readings --
    def readings(self):
        """The program's side of the training check, as host numbers."""
        check = jax.device_get(self.check)
        self.check = None
        return {"losses": [float(x) for x in check["loss"]],
                "grad_norms": self.ref.leaf_values(check["grad"]),
                "change_norms": self.ref.leaf_values(check["change"]),
                "row_norms": [np.asarray(x) for x in check["row_norms"]]}

    def window_s(self):
        return self.t_end - self.t_start

    def window_events(self, kind):
        return [r for r in getattr(self, kind) if r["window"]]

    def step_s(self):
        """Mean seconds per step in the window, events left out."""
        ev = sum(r["s"] for r in self.window_events("saves")) + \
            sum(r["s"] for r in self.window_events("failures"))
        return max(self.window_s() - ev, 1e-9) / max(self.window_steps, 1)


@jax.jit
def rows_changed(a, b):
    """Rows of the arrays of ``a`` that differ from those of ``b``."""
    return sum(jnp.count_nonzero((x != y).reshape(x.shape[0], -1).any(axis=1))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@jax.jit
def take_rows(tables, accs, rows):
    """The rows of every table and accumulator, stacked: two transfers."""
    return (jnp.stack([x[r] for x, r in zip(tables, rows)]),
            jnp.stack([x[r] for x, r in zip(accs, rows)]))


@jax.jit
def state_digest(arrays):
    """Per float32 array, the sum of its words times the odd weights 2i + 1,
    modulo 2**32: a single changed word always changes it."""
    def one(x):
        w = jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(-1)
        i = jnp.arange(w.size, dtype=jnp.uint32)
        return jnp.sum(w * (2 * i + 1), dtype=jnp.uint32)
    return jnp.stack([one(x) for x in arrays])


@jax.jit
def acc_grad_norms(acc, params):
    """Per-leaf gradient norm of a first row-wise Adagrad step, from its
    state: a 2-D leaf's accumulator is the row mean of the squared gradient,
    a 1-D leaf's the squared gradient."""
    return jax.tree.map(
        lambda a, p: jnp.sqrt(jnp.sum(a) * (p.size // p.shape[0]
                                            if p.ndim >= 2 else 1)),
        acc, params)


# ------------------------------------------------------------- the cell ----
def shard_sets(seed, n_events, n_shards, per_event):
    rng = np.random.default_rng([int(seed), 0xFA11])
    return [tuple(sorted(int(j) for j in rng.choice(n_shards, per_event,
                                                   replace=False)))
            for _ in range(n_events)]


class Cell:
    def __init__(self, cfg, traffic, seed, faults=()):
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.faults = tuple(faults)
        self.dcfg = program_config(cfg)
        self.ref = reference(cfg["arch"])
        self.arch = self.ref.Arch.of(cfg)
        self.batch = int(cfg["batch_size"])
        p = traffic["pool"]
        self.n_train_batches = int(p["train_batches"])
        self.pool = pool_mod.make_pool(
            cfg["table_sizes"], cfg["num_dense"],
            self.n_train_batches * self.batch + int(p["eval_samples"]),
            self.seed, zipf_a=p["zipf_a"], multi_hot=self.dcfg.multi_hot)
        sp = self.pool_batch(0)["sparse"]
        # the rows step 0 touches: the check compares their first gradients
        self.first_rows = [np.unique(sp[:, t, :])
                           for t in range(sp.shape[1])]
        self.image_rows = None
        if traffic["mode"] == "full":
            # the rows each full save's check reads: a table's ids in
            # IMAGE_SAMPLES pool samples drawn from the seed, so the rows
            # that training moves are in it
            rng = np.random.default_rng([self.seed, 0x1A6E])
            pick = rng.integers(0, self.n_train_batches * self.batch,
                                IMAGE_SAMPLES)
            self.image_rows = [self.pool["sparse"][pick, t, 0]
                               for t in range(sp.shape[1])]

    def probes(self, **kw):
        return Probes(self.arch, self.ref, self.seed, self.first_rows,
                      self.faults, image_rows=self.image_rows, **kw)

    def pool_batch(self, i):
        """The batch that step ``i`` of a run is fed."""
        return pool_mod.PoolDataset(self.pool, self.batch,
                                    self.n_train_batches).train_batch(i)

    def _program(self, span_h, failures_h, probes, slack=SPAN_SLACK):
        from repro.core import (CPRManager, Emulator, FailureInjector,
                                SystemParams)
        tr = self.traffic
        sp = SystemParams(T_total=span_h * (1 + slack), **tr["system"])
        mgr = CPRManager(tr["mode"], sp, self.cfg["table_sizes"],
                         target_pls=tr["target_pls"], r=tr["r"],
                         ssu_period=tr["ssu_period"],
                         tracker_backend=tr["tracker_backend"],
                         seg_size=tr["seg_size"])
        k = int(tr["failed_shards"])
        inj = FailureInjector(len(failures_h), k / sp.N_emb, sp.N_emb,
                              sp.T_total, times=failures_h,
                              shard_sets=shard_sets(self.seed,
                                                    len(failures_h),
                                                    sp.N_emb, k))
        probes.wrap_manager(mgr, inj)

        class ProbedEmulator(Emulator):
            def _build_step(self):
                step, opt = super()._build_step()
                return probes.wrap_step(step), opt

        ds = pool_mod.PoolDataset(self.pool, self.batch, self.n_train_batches,
                                  on_eval=probes.on_eval,
                                  annotate=probes.jax.profiler.TraceAnnotation)
        opt = self.cfg["optimizer"]
        return ProbedEmulator(self.dcfg, ds, mgr, inj, batch_size=self.batch,
                              lr=opt["lr"], seed=self.seed)

    def run(self, steps, span_h, failures_h, record=False, trace_dir=None):
        """One ``Emulator.run`` under probes -> (probes, emulator, result)."""
        probes = self.probes(record=record, trace_dir=trace_dir)
        emu = self._program(span_h, failures_h, probes)
        with jax.default_matmul_precision(self.cfg["matmul_precision"]):
            res = emu.run(max_steps=steps)
        return probes, emu, res

    def first_steps(self):
        """The program's readings of steps 0-2 alone, through the window
        run's own path, stopped before the window -> check dict."""
        probes = self.probes()
        probes.stop_at_window = True
        tr = self.traffic
        emu = self._program(tr["span_h"], tr["failures_h"], probes)
        try:
            with jax.default_matmul_precision(self.cfg["matmul_precision"]):
                emu.run(max_steps=1 << 20)
        except StopRun:
            pass
        probes.latest = None
        return probes.readings()

    def warm_up(self):
        """A short run whose span holds saves and a failure.  Only its first
        save runs (a cycle boundary adds host work, no program); the others
        return the tracker untouched.  Its span and steps are binary-exact
        (6 h in 16 steps), so the emulator's summed clock lands on every
        save time."""
        w = self.traffic["warmup"]
        probes = self.probes()

        probes.run_saves = lambda index, t_event: index == 0
        emu = self._program(w["span_h"], w["failures_h"], probes, slack=0.0)
        with jax.default_matmul_precision(self.cfg["matmul_precision"]):
            emu.run(max_steps=int(w["steps"]))
        emu.final_params = emu.final_ostate = None
        probes.latest = None
        return probes

    def size(self, seconds):
        """A fixed amount of training for ``seconds``: the cell's
        ``steps_per_second`` (its step rate on the chip when the benchmark
        was defined) times the seconds.  The span's saves and failures come
        on top; a faster program finishes the same work sooner."""
        n = int(round(self.traffic["steps_per_second"] * seconds))
        return WINDOW_STEP + max(int(self.traffic["min_window_steps"]), n)


def expected_events(traffic):
    """Saves and failures the window's span holds, by the paper's policy
    (``SystemParams`` defaults, Eq. 1 and Eq. 4)."""
    import math
    s = traffic["system"]
    ts_full = math.sqrt(2 * s["O_save"] * s["T_fail"])
    span = traffic["span_h"]
    if traffic["mode"] == "full":
        interval = ts_full
    else:
        interval = min(2 * traffic["target_pls"] * s["N_emb"] * s["T_fail"],
                       span) / round(1 / traffic["r"])
    return (int(span * (1 + SPAN_SLACK) / interval + 1e-9),
            len(traffic["failures_h"]))
