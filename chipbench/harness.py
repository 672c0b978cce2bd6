"""One run of one benchmark cell: set-up, measured window, trace, check.

Everything that belongs to one configuration, traffic mix, metric or
kernel lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``configs/<config>.json``: sizes, method, precision, sources, and the
  name of the client population (``population``, ``mnist-shards`` where
  the file names none);
* ``populations/<population>.py``: ``make(config, key)``, the per-client
  arrays from the seed, a dict of arrays with leading axes [N, n];
* ``families/<family>.py``: the program's problem for that family, built
  from that dict, the model's operation counts and, optionally, the Pallas
  kernels its own layers call (``kernel_calls``); ``reference/<family>.py``:
  its plain loss, parameters and the arrays it takes from the dict, and,
  optionally, ``frozen(config, key)``: weights the algorithm does not
  update (a frozen base under trained adapters), made on the device from
  the seed in the configuration's dtype, which ``build_problem`` then gets
  as ``frozen=`` and the reference as an argument of every step;
* the configuration's optional ``eval_per_client``: how many leading
  samples of each client the global loss reads (all where it names none).
  The reference reads that slice; a family whose problem evaluates on
  fewer samples than a client holds makes the program's global loss read
  the same slice;
* ``traffic/<mix>.json``: participation, comm legs, selection policy, grid
  shape, rounds, chips, how many cells the check replays;
* ``limits/<cell>.json``: the limit of every number the check compares;
* ``metrics/<metric>.py``: a reader ``read(ctx)`` returning the metric or
  None where it finds nothing to read;
* ``kernels/<kernel>.py``: ``cost(cells, rows, width)`` -> (flops, bytes).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import statistics
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")

# what may not happen inside the measured window: a backend compile (or a
# persistent-cache read, which is recorded under the same event). Small
# jaxpr traces are counted apart: the sweep's host path re-traces its eager
# ``vmap`` helpers (the comm mask schedule) on every call, without compiling.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
DEFAULT_POPULATION = "mnist-shards"
FROZEN_TAG = 0xF207E  # folded into the data key for the frozen weights


# -- lookup by name ----------------------------------------------------------

def _path(kind: str, name: str, ext: str) -> str:
    path = os.path.join(HERE, kind, name + ext)
    if not os.path.isfile(path):
        raise LookupError(f"no {kind[:-1] if kind.endswith('s') else kind} "
                          f"named {name!r} ({path} does not exist)")
    return path


def load_json(kind: str, name: str) -> dict:
    with open(_path(kind, name, ".json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = _path(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(path: str = BENCHMARK) -> dict:
    with open(path) as f:
        return json.load(f)


def workload(name: str, bench: dict) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise LookupError(f"no workload named {name!r} in BENCHMARK.json; known: "
                      f"{[w['name'] for w in bench['workloads']]}")


def cell_metrics(name: str, bench: dict, trace: bool) -> list:
    """The metric entries a run of cell ``name`` reports."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


# -- seeds -------------------------------------------------------------------

def call_seeds(seed: int, call: int, n: int):
    """(cell seeds, mask seed, selection seed) of grid call ``call``: fresh
    for every call, all from ``--seed``."""
    state = np.random.SeedSequence([seed % 2**64, call]).generate_state(
        n + 2, np.uint32)
    vals = [int(v) & 0x7FFFFFFF for v in state]
    return tuple(vals[:n]), vals[n], vals[n + 1]


def data_seed(seed: int) -> int:
    return int(np.random.SeedSequence([seed % 2**64, 0xDA7A]).generate_state(
        1, np.uint32)[0]) & 0x7FFFFFFF


# -- the cell ----------------------------------------------------------------

@dataclasses.dataclass
class Call:
    index: int
    seeds: tuple
    mask_seed: int
    sel_seed: int
    t_start: float
    t_return: float
    t_done: float
    history: np.ndarray  # [cells, R]
    final: np.ndarray  # [cells]
    bits_up: np.ndarray  # [cells, R]
    bits_down: np.ndarray  # [cells, R]
    result: object  # the SweepResult, on the device
    ok: bool


class Cell:
    """A configuration under a traffic mix, built on the program."""

    def __init__(self, name: str, bench: dict | None = None, *,
                 config: dict | None = None, traffic: dict | None = None,
                 limits: dict | None = None):
        bench = benchmark() if bench is None else bench
        self.name = name
        self.workload = workload(name, bench)
        if config is None:
            entry = next(c for c in bench["configs"]
                         if c["name"] == self.workload["config"])
            with open(os.path.join(ROOT, entry["file"])) as f:
                config = json.load(f)
        self.config = config
        self.traffic = (load_json("traffic", self.workload["traffic"])
                        if traffic is None else traffic)
        self.limits = load_json("limits", name) if limits is None else limits
        self.population = load_module(
            "populations", config.get("population", DEFAULT_POPULATION))
        self.family = load_module("families", config["family"])
        self.model = load_module("reference", config["family"])
        self.chips = int(self.workload["chips"])
        t = self.traffic
        self.mults = tuple(float(m) for m in t["multipliers"])
        self.n_seeds = int(t["seeds_per_call"])
        self.cells = self.n_seeds * len(self.mults)
        self.rounds = int(t["rounds"])

    # -- program side ------------------------------------------------------

    def setup(self, seed: int):
        """Population, weights (frozen ones too, where the reference makes
        them), problem, method and mesh from ``seed``."""
        import jax
        import jax.numpy as jnp

        from repro.core import algorithms as A, chain

        # the configuration's precision, for everything the program traces
        jax.config.update("jax_default_matmul_precision",
                          self.config["matmul_precision"])
        key = jax.random.PRNGKey(data_seed(seed))
        k_pop, k_init = jax.random.split(key)
        self.data = self.population.make(self.config, k_pop)
        self.x0 = self.model.init(self.config, k_init, jnp.float32)
        self.frozen = None
        extra = {}
        if hasattr(self.model, "frozen"):
            self.frozen = self.model.frozen(
                self.config, jax.random.fold_in(key, FROZEN_TAG))
            extra["frozen"] = self.frozen
        self.problem = self.family.build_problem(self.config, self.data,
                                                 self.x0, **extra)
        self.x0 = self.problem.x0
        m = self.config["method"]
        loc, glob = m["local"], m["global"]
        self.method = chain.fedchain(
            A.FedAvg(eta=loc["eta"], k=loc["k"],
                     local_steps=loc["local_steps"],
                     inner_batch=loc["inner_batch"]),
            A.SGD(eta=glob["eta"], k=glob["k"], mu_avg=glob["mu_avg"],
                  output_mode=glob["output_mode"]),
            local_fraction=m["local_fraction"],
            selection_k=m["selection_k"], name=self.config["name"])
        self.mesh = None
        if self.chips > 1:
            from repro.dist import make_grid_mesh

            self.mesh = make_grid_mesh(self.chips)
        self.seed = seed

    def request(self, call: int):
        from repro.comm import CommPlan, Leg
        from repro.core import sweep
        from repro.selection import SelectionPolicy

        t = self.traffic
        seeds, mask_seed, sel_seed = call_seeds(self.seed, call, self.n_seeds)
        pol = t.get("policy")
        plan = CommPlan(
            uplink=Leg(**t["uplink"]), downlink=Leg(**t["downlink"]),
            participation=1.0 if pol else float(t["participation"]),
            mask_seed=mask_seed)
        policies = None
        if pol:
            policies = (SelectionPolicy(
                pol["name"], participation=float(t["participation"]),
                ucb_c=float(pol.get("ucb_c", 1.0)), sel_seed=sel_seed),)
        req = sweep.SweepRequest(
            algo_or_chain=self.method, problem=self.problem, x0=self.x0,
            rounds=self.rounds, seeds=seeds, etas=self.mults, comm=plan,
            policies=policies, mesh=self.mesh)
        return req, seeds, mask_seed, sel_seed

    def grid_call(self, call: int) -> Call:
        """One closed-loop call: build, run, fetch the results to the host."""
        import jax
        from repro.core import sweep

        req, seeds, mask_seed, sel_seed = self.request(call)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.sweep_run"):
            res = sweep.run(req)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.fetch"):
            hist = np.asarray(res.history).reshape(self.cells, self.rounds)
            final = np.asarray(res.final_sub).reshape(self.cells)
            bu = np.asarray(res.bits_up).reshape(self.cells, self.rounds)
            bd = np.asarray(res.bits_down).reshape(self.cells, self.rounds)
        t2 = time.perf_counter()
        ok = bool(np.isfinite(hist).all() and np.isfinite(final).all())
        return Call(call, seeds, mask_seed, sel_seed, t0, t1, t2, hist, final,
                    bu, bd, res, ok)

    # -- the check ---------------------------------------------------------

    def cell_output(self, call: Call, c: int):
        """The program's x_hat, selection decision and masks of cell ``c``."""
        import jax

        lead = call.result.history.ndim - 1  # axes before R
        def pick(a):
            a = np.asarray(a)
            return a.reshape((self.cells,) + a.shape[lead:])[c]
        x_hat = jax.tree.map(pick, call.result.x_hat)
        kept = bool(pick(call.result.selected_initial).reshape(-1)[0])
        masks = (pick(call.result.masks)
                 if getattr(call.result, "masks", None) is not None else None)
        return x_hat, kept, masks

    def reference(self, dtype=None, precision="highest", fault=None):
        """The plain reference over this cell's population (float32,
        ``highest`` products unless told otherwise)."""
        import jax.numpy as jnp

        from chipbench.reference.fedchain import Reference

        return Reference(self.model, self.config, self.traffic, self.data,
                         frozen=self.frozen,
                         dtype=jnp.float32 if dtype is None else dtype,
                         precision=precision, fault=fault)

    def replay(self, ref, call: Call, c: int):
        s, e = divmod(c, len(self.mults))
        return ref.run_cell(self.x0, seed=call.seeds[s], mult=self.mults[e],
                            mask_seed=call.mask_seed, sel_seed=call.sel_seed,
                            fold=s)

    def outcome(self, call: Call, c: int) -> dict:
        """What the program produced for cell ``c`` of ``call``."""
        x_hat, kept, masks = self.cell_output(call, c)
        return {"history": np.append(call.history[c], call.final[c]),
                "x_hat": x_hat, "bits_up": call.bits_up[c],
                "bits_down": call.bits_down[c], "kept": kept, "masks": masks}

    @staticmethod
    def replayed(r) -> dict:
        """The same for a replay (reference, control or planted fault)."""
        return {"history": np.append(r.history, r.history[-1]),
                "x_hat": r.x_hat, "bits_up": r.bits_up,
                "bits_down": r.bits_down, "kept": r.kept, "masks": r.masks,
                "margins": r.margins}

    def compare(self, got: dict, want: dict) -> dict:
        """The numbers of one cell, ``got`` against the reference ``want``:

        * loss_gap: the largest relative gap of a round's global loss (and
          of the final suboptimality);
        * change_gap: per leaf, the gap between the norms of the change
          x_hat - x0, over the reference's norm of that leaf or of the
          median leaf, whichever is larger; the worst leaf;
        * bits_gap: the largest gap of a round's uplink or downlink bits;
        * selection_mismatch: 1 where the Lemma H.2 selection differs.

        Under a selection policy the participants of a round depend on
        probed losses, and a top-S choice at a near-tie flips on rounding;
        the trajectories then part. So there the rounds are compared up to
        the first round whose participants differ, and that round's flip is
        held to be a near-tie:

        * flip_margin: the reference's relative gap between the S-th and
          the next score at the first differing round (0 where none
          differs; +inf where the choice was not a matter of score);
        * compared_rounds (reported, not limited): rounds before it.
        The final iterate and the selection row are compared only where no
        round differed."""
        import jax

        rounds = len(want["bits_up"])
        out = {}
        first = rounds
        if self.traffic.get("policy"):
            differ = np.any(got["masks"] != want["masks"], axis=1)
            first = int(np.argmax(differ)) if differ.any() else rounds
            out["flip_margin"] = (0.0 if first == rounds
                                  else float(want["margins"][first]))
        n = first + 1 if first == rounds else first  # + the final value
        hg, hw = got["history"][:n], want["history"][:n]
        out["loss_gap"] = (float(np.max(np.abs(hg - hw) / np.abs(hw)))
                           if n else 0.0)
        if first == rounds:
            x0 = jax.tree.map(np.asarray, self.x0)
            cg = [float(np.linalg.norm(np.asarray(a, np.float32) - b))
                  for a, b in zip(jax.tree.leaves(got["x_hat"]),
                                  jax.tree.leaves(x0))]
            cw = [float(np.linalg.norm(np.asarray(a, np.float32) - b))
                  for a, b in zip(jax.tree.leaves(want["x_hat"]),
                                  jax.tree.leaves(x0))]
            floor = float(np.median(cw))
            out["change_gap"] = max(abs(p - q) / max(q, floor)
                                    for p, q in zip(cg, cw))
        else:
            out["change_gap"] = 0.0
        out["bits_gap"] = float(max(
            np.max(np.abs(got["bits_up"] - want["bits_up"])),
            np.max(np.abs(got["bits_down"] - want["bits_down"]))))
        sel_row = rounds // 2  # the Lemma H.2 row (after round(R/2) rounds)
        out["selection_mismatch"] = (int(got["kept"] != want["kept"])
                                     if first > sel_row else 0)
        if self.traffic.get("policy"):
            out["compared_rounds"] = first
        return out

    @staticmethod
    def worst(rows) -> dict:
        """Worst of each number over cells: gaps by max, mismatches summed,
        compared rounds by min.

        Besides, ``least_cell_loss_gap`` and ``least_cell_change_gap``: the
        least over cells of each cell's gap. Now and then a last-ulp
        difference flips one QSGD level in one cell, and that cell's
        trajectory parts from the reference's for the rest of the run; a
        lower precision or a fault parts every cell's. The least over cells
        reads the second and not the first."""
        rows = list(rows)
        out = {}
        for row in rows:
            for k, v in row.items():
                if k.endswith("mismatch"):
                    out[k] = out.get(k, 0) + v
                elif k == "compared_rounds":
                    out[k] = min(v, out.get(k, v))
                else:
                    out[k] = max(v, out.get(k, v))
        for k in ("loss_gap", "change_gap"):
            if any(k in row for row in rows):
                out["least_cell_" + k] = min(row[k] for row in rows
                                             if k in row)
        return out

    def sample(self, calls: list, seed: int) -> list:
        """(call, cell) pairs the check replays, drawn from the seed."""
        rng = np.random.default_rng(
            np.random.SeedSequence([seed % 2**64, 0xC4EC]))
        pairs = [(i, c) for i in range(len(calls)) for c in range(self.cells)]
        n = min(int(self.traffic["check_cells"]), len(pairs))
        picks = rng.choice(len(pairs), size=n, replace=False)
        return [pairs[int(p)] for p in sorted(picks)]

    def numbers(self, calls: list, seed: int, ref) -> dict:
        """Worst of every compared number over the sampled cells."""
        return self.worst(
            self.compare(self.outcome(calls[i], c),
                         self.replayed(self.replay(ref, calls[i], c)))
            for i, c in self.sample(calls, seed))


# -- one run -----------------------------------------------------------------

def enable_compile_cache():
    """JAX's persistent cache at a fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts compiles and jaxpr traces while ``armed``; sums the seconds of
    every compile-path event."""

    def __init__(self):
        import jax

        self.armed = False
        self.compiles = 0
        self.traces = 0
        self.seconds = {}
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kw):
        self.seconds[event] = self.seconds.get(event, 0.0) + duration
        if self.armed:
            self.compiles += event == _COMPILE_EVENT
            self.traces += event == _TRACE_EVENT


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run(name: str, seed: int, seconds: float, trace: bool, *, t_start: float,
        cell: Cell | None = None, bench: dict | None = None,
        log=sys.stderr) -> dict:
    """One run; returns the result line's object."""
    import jax

    from repro.core import runner

    bench = benchmark() if bench is None else bench
    enable_compile_cache()
    counter = CompileCounter()
    cell = Cell(name, bench) if cell is None else cell
    cell.setup(seed)
    t0 = time.perf_counter()
    warm = cell.grid_call(0)
    warmup_s = time.perf_counter() - t0
    setup_s = time.perf_counter() - t_start
    log.write(f"setup: {setup_s:.3f} s, first grid call {warmup_s:.3f} s, "
              f"compile events {json.dumps(counter.seconds)}\n")

    calls = []
    before = runner.snapshot_traces()
    counter.armed = True
    t_w = time.perf_counter()
    while time.perf_counter() - t_w < seconds:
        try:
            calls.append(cell.grid_call(len(calls) + 1))
        except Exception as e:  # a failed call is counted, then reported
            log.write(f"grid call {len(calls) + 1} raised {e!r}\n")
            calls.append(None)
            break
    counter.armed = False
    done = [c for c in calls if c is not None]
    t_end = done[-1].t_done if done else time.perf_counter()
    retraces = runner.trace_deltas(before)
    log.write(f"window: {len(calls)} grid calls in {t_end - t_w:.3f} s, "
              f"{counter.compiles} compiles, executor re-traces {retraces}, "
              f"{counter.traces} small jaxpr traces on the host path\n")
    if counter.compiles or retraces:
        raise RuntimeError("something compiled or re-traced an executor "
                           "inside the measured window")
    dev = device_info(cell.chips)

    ctx = {
        "cell": cell, "calls": done, "warmup_s": warmup_s,
        "setup_s": setup_s, "window_s": t_end - t_w,
        "cell_rounds": len(done) * cell.cells * cell.rounds,
        "device": dev, "chips": cell.chips, "trace": None, "log": log,
    }
    breakdown = None
    if trace:
        tr, traced = traced_window(cell, len(calls) + 1, log)
        ctx["trace"], ctx["traced_calls"] = tr, traced
        dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}

    metrics = {}
    for m in cell_metrics(name, bench, trace):
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the check: program state freed but for the sampled cells' results
    keep = {i for i, _ in cell.sample(done, seed)}
    for i, c in enumerate(done):
        if i not in keep:
            c.result = None
    warm.result = None
    jax.clear_caches()
    t_c = time.perf_counter()
    numbers = cell.numbers(done, seed, cell.reference()) if done else {}
    checks = {k: {"value": v, "limit": cell.limits[k]}
              for k, v in numbers.items() if k in cell.limits}
    failed = sum(1 for c in calls if c is None or not c.ok)
    correct = (bool(done) and failed == 0
               and all(v["value"] <= v["limit"] for v in checks.values()))
    log.write(f"check: {len(cell.sample(done, seed))} cells replayed in "
              f"{time.perf_counter() - t_c:.3f} s\n")
    for k, v in numbers.items():
        if k not in checks:
            log.write(f"check {k} = {v!r} (reported)\n")
    for k, v in checks.items():
        log.write(f"check {k} = {v['value']!r} limit {v['limit']!r}\n")
    out = {"correct": correct, "attempted": len(calls), "failed": failed,
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def traced_window(cell: Cell, first_call: int, log, *, min_calls: int = 2,
                  min_seconds: float = 2.0):
    """Profile a few more grid calls; reduce the trace to numbers."""
    import jax

    from chipbench import trace as trace_lib

    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as d:
        jax.profiler.start_trace(d)
        try:
            calls = []
            t0 = time.perf_counter()
            while (len(calls) < min_calls
                   or time.perf_counter() - t0 < min_seconds):
                calls.append(cell.grid_call(first_call + len(calls)))
        finally:
            jax.profiler.stop_trace()
        t_parse = time.perf_counter()
        devices, spans = trace_lib.extract(trace_lib.find_xplane(d))
    runs = [s for s in spans if s[2] == "chipbench.sweep_run"]
    fetches = [s for s in spans if s[2] == "chipbench.fetch"]
    window = (min(s[0] for s in runs), max(s[1] for s in fetches))
    kernels = sorted(os.path.splitext(f)[0] for f in
                     os.listdir(os.path.join(HERE, "kernels"))
                     if f.endswith(".py") and not f.startswith("_"))
    tr = trace_lib.reduce(devices, spans, window, kernels)
    tr["host_span_s"] = statistics.fmean(b - a for a, b, _ in runs) * 1e-9
    log.write(f"trace: {len(calls)} grid calls, {sum(map(len, devices.values()))}"
              f" device ops on {len(devices)} devices, read in "
              f"{time.perf_counter() - t_parse:.3f} s; kernel events "
              f"{tr['kernel_events']}\n")
    return tr, calls
