"""One run of one cell: set-up, the measured window, the check against the
reference, and the traced run's per-layer metrics.

Everything here is a plain function of the cell's files, so tests run it at
a tiny width on the CPU. Nothing branches on a cell's name and nothing here
reads a model's shape: each per-layer metric is a reader
``bench/metrics/<name>.py``, and the configuration's ``family`` names the
three files that hold all that the benchmark knows of a model family
(``bench/families.py`` loads them by name). A family is added as these new
files alone; no file the benchmark has is edited for it.

``bench/drivers/<family>.py``, the system under test, built from the
program's public constructors:

- ``check_layout(cfg, stage, frozen, active, state)`` raises unless the
  benchmark's weights have the program's layout and shapes;
- ``System(cfg, traffic, frozen, state, pool_x, pool_y, seeds, mesh=None,
  compute_dtype=None)``, with ``fill_cache()`` (the bytes it cached),
  ``run_round(cohort, round_idx, params, state)`` (the new params and
  state and the cohort's losses) and ``close()``.

``bench/references/<family>.py``, the plain reference, which imports
nothing of the program:

- ``skew_classes(cfg)``: the number of classes (or topics) that the pool's
  Dirichlet label skew is drawn over;
- ``make_inputs(cfg, traffic, labels, key, chunk)``: the inputs of one
  chunk of the pool's samples, ``[n, ...]`` of any shape and dtype, traced
  under ``jax.jit``;
- ``init_weights(cfg, stage, key)``: ``(frozen, active, state)``, traced
  under ``jax.jit``;
- ``batch_plan(n, batch, epochs, seed)`` and ``round_seed(client_seed,
  round_idx)``: a client's minibatch indices, as the program draws them;
- ``Reference(cfg, stage, lr=..., clip_norm=...)`` with ``round(frozen,
  active, state, data, plans, weights)``: ``(active, state, losses)``, where
  ``data[i]`` is client i's ``{"x": inputs, "y": labels}``;
  ``bench/control.py`` plants its faults in its ``_local_train(frozen,
  active, state, xs, ys)``, ``clients``, ``fold`` and ``_precision``;
- ``tiny(cfg)``: the configuration cut to a size that the benchmark's CPU
  tests run in seconds.

``bench/counts/<family>.py``: ``flops_per_round(cfg, traffic)``, the
counted FLOPs of one round by ``bench/flops.py``'s rule.
"""
from __future__ import annotations

import gc
import json
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import check, flops, trace as trace_mod, world
from bench.families import BENCH, load_module, module

GIB = float(2 ** 30)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a traced run measures its whole window untraced, as an untraced run
# does, and then traces this much more: the profiler slows the host's part
# of a round two- to threefold, and ten seconds of ResNet-18 rounds are
# ~0.23 GB of device events
TRACE_SECONDS = 3.0


def cell_files(root: Path, spec: Dict, workload: str) -> Dict:
    """The cell's configuration, traffic, limits and metric lists, found by
    the names in ``BENCHMARK.json``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; choose from "
                       f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = root / spec["paths"][0]

    def listed(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell,
        "config": json.loads((root / configs[cell["config"]]["file"])
                             .read_text()),
        "traffic": json.loads((bench / "traffic" / f"{cell['traffic']}.json")
                              .read_text()),
        "limits": json.loads((bench / "limits" / f"{workload}.json")
                             .read_text()),
        "end_to_end": [m["name"] for m in spec["end_to_end"] if listed(m)],
        "per_layer": [m["name"] for m in spec["per_layer"] if listed(m)],
        "units": {m["name"]: m["unit"]
                  for m in spec["end_to_end"] + spec["per_layer"]},
    }


def peak_of(kind: str) -> Dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"{BENCH / 'peaks.json'}; known: {sorted(peaks)}")
    return peaks[kind]


class CompileCounter:
    """Counts executables built or loaded while ``on``."""

    def __init__(self):
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and name == COMPILE_EVENT:
            self.count += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._event)


def host_copy(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def make_pool(ref, cfg: Dict, traffic: Dict, labels: np.ndarray,
              seed: int) -> np.ndarray:
    """The pool's inputs on the host, [clients, per_client, ...] as the
    family's ``make_inputs`` makes them, on the device in equal chunks of
    clients (one program)."""
    n_clients, per = labels.shape
    chunk = max(d for d in range(1, min(n_clients, 10) + 1)
                if n_clients % d == 0)
    gen = jax.jit(lambda y, k, i: ref.make_inputs(cfg, traffic, y, k, i))
    key = jax.random.PRNGKey(seed)
    out = None
    for i, lo in enumerate(range(0, n_clients, chunk)):
        y = labels[lo:lo + chunk].reshape(-1)
        x = np.asarray(gen(y, key, i))
        if out is None:
            out = np.empty((n_clients, per) + x.shape[1:], x.dtype)
        out[lo:lo + chunk] = x.reshape((chunk, per) + x.shape[1:])
    return out


class World:
    """What the seed makes for a cell, apart from the program: the pool's
    labels and inputs, the client seeds, the weights and the cohorts."""

    def __init__(self, files: Dict, seed: int):
        cfg, traffic = files["config"], files["traffic"]
        self.cfg, self.traffic = cfg, traffic
        self.ref = module(cfg["family"], "references")
        streams = world.streams(seed)
        n_clients, per = traffic["clients"], traffic["samples_per_client"]
        self.labels = world.label_skew(n_clients, per,
                                       self.ref.skew_classes(cfg),
                                       traffic["alpha"], streams["labels"])
        self.pool_x = make_pool(self.ref, cfg, traffic, self.labels,
                                world.jax_seed(streams["inputs"]))
        self.seeds = world.client_seeds(n_clients, streams["clients"])
        self.frozen, self.active, self.state = jax.jit(
            lambda k: self.ref.init_weights(cfg, traffic["stage"], k))(
            jax.random.PRNGKey(world.jax_seed(streams["weights"])))
        self.start = host_copy((self.active, self.state))
        self.frozen_host = host_copy(self.frozen)
        self.cohorts = world.cohorts(n_clients, traffic["cohort"],
                                     streams["cohorts"])
        self.lead_cohorts = [next(self.cohorts)
                             for _ in range(traffic["lead_rounds"])]

    def reference(self, cls=None):
        """The reference for this cell (``cls``: a subclass of it)."""
        t = self.traffic
        cls = cls or self.ref.Reference
        return cls(self.cfg, t["stage"], lr=t["lr"],
                   clip_norm=t["clip_norm"])

    def reference_rounds(self, reference=None) -> List:
        """``reference``'s lead rounds from the start weights (the float32
        reference by default): ``[(params, state, losses), ...]``."""
        t = self.traffic
        reference = reference or self.reference()
        active, state = self.start
        n = t["samples_per_client"]
        out = []
        for r, cohort in enumerate(self.lead_cohorts):
            data = [{"x": self.pool_x[c], "y": self.labels[c]}
                    for c in cohort]
            plans = [self.ref.batch_plan(n, t["batch"], t["epochs"],
                                         self.ref.round_seed(self.seeds[c], r))
                     for c in cohort]
            active, state, losses = reference.round(
                self.frozen_host, active, state, data, plans,
                [n] * len(cohort))
            out.append((active, state, losses))
        return out


def run_cell(files: Dict, *, seed: int, seconds: float, chips: int,
             trace_dir: Optional[Path] = None, t_start: float,
             devices=None) -> Dict:
    """One run of the cell described by ``files`` (see ``cell_files``).
    Returns the result line as a dict, with every check's number beside
    its limit under ``checks``, and ``notes`` for the log."""
    cfg, traffic, limits = files["config"], files["traffic"], files["limits"]
    devices = list(devices if devices is not None else jax.devices())[:chips]
    driver = module(cfg["family"], "drivers")
    mesh = None
    if chips > 1:
        from repro.launch.mesh import make_client_mesh
        mesh = make_client_mesh(chips, devices=devices)

    # ----- set-up: world, engine, cache, the lead rounds -----
    # where set-up's seconds go, for the log: start-up and imports, the
    # world, the engine with its cache, the lead rounds
    marks = [time.perf_counter()]
    w = World(files, seed)
    driver.check_layout(cfg, traffic["stage"], w.frozen, w.active, w.state)
    marks.append(time.perf_counter())
    system = driver.System(cfg, traffic, w.frozen, w.state, w.pool_x,
                           w.labels, w.seeds, mesh=mesh)
    cache_bytes = system.fill_cache()
    marks.append(time.perf_counter())
    params, state = w.active, w.state
    del w.frozen, w.active, w.state
    lead: List = []
    for r, cohort in enumerate(w.lead_cohorts):
        params, state, losses = system.run_round(cohort, r, params, state)
        lead.append(host_copy((params, state)) + (losses,))
    marks.append(time.perf_counter())

    # ----- the measured window -----
    counter = CompileCounter()
    r = len(lead)

    def window(length: float):
        """Rounds back to back until ``length`` seconds have passed:
        (start, [(t0, t1, losses), ...])."""
        nonlocal params, state, r
        spans: List = []
        t_w0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            while True:
                cohort = next(w.cohorts)
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(trace_mod.ROUND_SPAN):
                    params, state, losses = system.run_round(
                        cohort, r, params, state)
                    jax.block_until_ready((params, state))
                t1 = time.perf_counter()
                spans.append((t0, t1, losses))
                r += 1
                if t1 - t_w0 >= length:
                    return t_w0, spans

    counter.on = True
    t_w0, spans = window(seconds)
    setup_s = t_w0 - t_start
    window_s = spans[-1][1] - t_w0
    traced: List = []
    if trace_dir is not None:
        # the device's ops and the host's own annotations (this module's
        # spans, compiles); no Python call tracing, which would slow the
        # host several times over
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        _, traced = window(TRACE_SECONDS)
        jax.profiler.stop_trace()
    counter.on = False
    counter.close()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    failed = sum(1 for *_, ls in spans + traced
                 if not np.isfinite(ls).all())
    final_finite = all(np.isfinite(np.asarray(x)).all()
                       for x in jax.tree.leaves((params, state)))

    # ----- free the program's state, then the reference -----
    system.close()
    del system, params, state
    gc.collect()
    numbers = check.compare(check.summary(w.start, lead),
                            check.summary(w.start, w.reference_rounds()))
    # the cell's limits file names the numbers it compares
    checks = {k: {"value": numbers[k]["value"], "limit": limits[k]}
              for k in limits}
    correct = (all(c["value"] <= c["limit"] for c in checks.values())
               and failed == 0 and final_finite)

    # ----- the result line -----
    dev = devices[0]
    n_rounds = len(spans)
    walls = np.asarray([b - a for a, b, _ in spans])
    e2e = {
        "setup_s": (setup_s, "s"),
        "samples_per_s": (n_rounds * flops.samples_per_round(traffic)
                          / window_s, "samples/s"),
        "round_ms_p90": (float(np.percentile(walls, 90)) * 1e3, "ms"),
        "peak_hbm_gib": (peak / GIB, "GiB"),
    }
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": n_rounds + len(traced),
              "failed": failed}
    notes = {"rounds": n_rounds, "window_s": window_s,
             "round_ms_median": float(np.median(walls)) * 1e3,
             "cache_bytes": cache_bytes,
             "setup_split_s": [round(b - a, 3) for a, b in
                               zip([t_start] + marks, marks)],
             "compiles_in_window": counter.count,
             "leaves_compared": numbers["leaves_compared"],
             # every number the comparison has, compared or not
             **{k: numbers[k]["value"] for k in check.NAMES},
             **{f"{k}_leaf": numbers[k]["leaf"] for k in check.NAMES
                if "leaf" in numbers[k]}}
    if trace_dir is None:
        result["metrics"] = {k: {"value": e2e[k][0], "unit": e2e[k][1]}
                             for k in files["end_to_end"]}
    else:
        # the profiler's cost: traced rounds against the untraced median
        notes["traced_round_ms_median"] = float(np.median(
            [b - a for a, b, _ in traced])) * 1e3
        reduced = trace_mod.reduce_xspace(trace_mod.find_xspace(
            str(trace_dir)))
        (Path(trace_dir) / "reduced.json").write_text(json.dumps(reduced))
        tr = trace_mod.Trace(reduced)
        # the untraced window's rounds and seconds beside the trace's
        ctx = {"trace": tr, "compiles": counter.count, "chips": chips,
               "rounds": n_rounds, "window_s": window_s,
               "flops_per_round": flops.flops_per_round(cfg, traffic),
               "peak": peak_of(dev.device_kind), "units": files["units"]}
        result["metrics"] = per_layer_metrics(files["per_layer"], ctx)
        device["busy_s"] = tr.busy_ns() * 1e-9
        device["window_s"] = tr.window_ns * 1e-9
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in tr.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in tr.gaps()[:10]]}
    result["device"] = device
    result["notes"] = notes
    result["checks"] = checks
    return result


def per_layer_metrics(names: List[str], ctx: Dict) -> Dict:
    """Each listed metric's reader, found by name; a reader that finds
    nothing to read returns None and its metric is left out."""
    out = {}
    for name in names:
        mod = load_module(BENCH / "metrics" / f"{name}.py",
                          f"bench_metric_{name}")
        value = mod.read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": ctx["units"][name]}
    return out

