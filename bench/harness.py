"""One run of one cell: set-up, the measured window, the check against the
reference, and the traced run's per-layer metrics.

Everything here is a plain function of the cell's files, so tests run it at
a tiny width on the CPU. Nothing branches on a cell's name: the
configuration's ``family`` names the program-side driver
(``bench/drivers/<family>.py``) and the plain reference
(``bench/references/<family>.py``), and each per-layer metric is a reader
``bench/metrics/<name>.py``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import check, flops, trace as trace_mod, world

BENCH = Path(__file__).resolve().parent
GIB = float(2 ** 30)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a traced run measures its whole window untraced, as an untraced run
# does, and then traces this much more: the profiler slows the host's part
# of a round two- to threefold, and ten seconds of ResNet-18 rounds are
# ~0.23 GB of device events
TRACE_SECONDS = 3.0


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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


def make_pool(ref, cfg: Dict, labels: np.ndarray, seed: int) -> np.ndarray:
    """The pool's images on the host, [clients, per_client, H, W, C], made
    on the device in equal chunks of clients (one program)."""
    n_clients, per = labels.shape
    chunk = max(d for d in range(1, min(n_clients, 10) + 1)
                if n_clients % d == 0)
    size, ch = cfg["image_size"], cfg["in_channels"]
    out = np.empty((n_clients, per, size, size, ch), np.float32)
    gen = jax.jit(lambda y, k, i: ref.make_images(cfg, y, k, i))
    key = jax.random.PRNGKey(seed)
    for i, lo in enumerate(range(0, n_clients, chunk)):
        y = labels[lo:lo + chunk].reshape(-1)
        out[lo:lo + chunk] = np.asarray(gen(y, key, i)).reshape(
            (chunk, per, size, size, ch))
    return out


class World:
    """What the seed makes for a cell, apart from the program: the pool's
    labels and images, the client seeds, the weights and the cohorts."""

    def __init__(self, files: Dict, seed: int):
        cfg, traffic = files["config"], files["traffic"]
        self.cfg, self.traffic = cfg, traffic
        self.ref = load_module(BENCH / "references" / f"{cfg['family']}.py",
                               f"bench_ref_{cfg['family']}")
        streams = world.streams(seed)
        n_clients, per = traffic["clients"], traffic["samples_per_client"]
        self.labels = world.label_skew(n_clients, per, cfg["num_classes"],
                                       traffic["alpha"], streams["labels"])
        self.pool_x = make_pool(self.ref, cfg, self.labels,
                                world.jax_seed(streams["images"]))
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
    driver = load_module(BENCH / "drivers" / f"{cfg['family']}.py",
                         f"bench_driver_{cfg['family']}")
    mesh = None
    if chips > 1:
        from repro.launch.mesh import make_client_mesh
        mesh = make_client_mesh(chips, devices=devices)

    # ----- set-up: world, engine, cache, the lead rounds -----
    w = World(files, seed)
    driver.check_layout(cfg, traffic["stage"], w.frozen, w.active, w.state)
    system = driver.System(cfg, traffic, w.frozen, w.state, w.pool_x,
                           w.labels, w.seeds, mesh=mesh)
    cache_bytes = system.fill_cache()
    params, state = w.active, w.state
    del w.frozen, w.active, w.state
    lead: List = []
    for r, cohort in enumerate(w.lead_cohorts):
        params, state, losses = system.run_round(cohort, r, params, state)
        lead.append(host_copy((params, state)) + (losses,))

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

