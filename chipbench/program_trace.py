"""The program's own spans and round-body scopes in a profiled stretch of
grid calls.

``trace.py`` keeps the benchmark's spans and names device ops by their HLO
instruction. The program marks more (``repro.obs``): host spans named
``repro.*`` in the sweep host path, with their arguments, and a
``jax.named_scope`` over each part of the round body, which reaches every
op's ``op_name`` metadata. This module reads both:

* a device op's scope is the first component of its name stack that is in
  ``SCOPES`` (transform wrappers such as ``vmap(local)`` peeled; a
  ``jit(...)`` component is a function, never a scope), or ``none``. On
  TPU v5e an "XLA Ops" event carries only its offset and duration, so the
  name stack comes from the HLO protos on the trace's ``/host:metadata``
  plane, one per module, matched to an op by the module event around it
  on its device's "XLA Modules" line (``hlo_op_names``). An op with no
  name stack of its own (one XLA made: the body of a gather it turned into
  a loop, a copy) takes the scope of the innermost while, conditional or
  call op around it.
* ``reduce``: ``scope_s`` (busy time per scope, summed over devices; each
  busy instant credited once, to the earliest-starting op that covers it,
  from the leaf ops and window ``trace.reduce`` takes busy time from),
  ``host_spans`` {name: [total_s, count]} and the idle gaps, each named by
  the innermost span open at its midpoint, program spans included.

``measure(ctx)`` makes the stretch once per traced run, for every reader
that needs it. It first drops the in-memory caches and calls the grid
again: the executor is traced and lowered anew, and those seconds are what
``executor_trace_lower_s`` reads. JAX leaves op metadata out of the
persistent compile cache's key, so the executable read back may carry
another build's metadata (a parent commit's, without scopes): where the
program defines scopes and no op in the trace has one, the executor is
compiled once more with metadata in the key and profiled again.
"""
from __future__ import annotations

import bisect
import functools
import json
import re
import tempfile
import time

from chipbench import trace

# the program's round-body scopes (``repro.obs.SCOPES``), fixed here: the
# names are part of what the metrics read
SCOPES = ("sample", "local", "downlink", "uplink", "server", "select",
          "eval", "taps")
NONE = "none"
SPAN_PREFIXES = (trace.SPAN_PREFIX, "repro.")
_INSTR = re.compile(r"^%?([^\s=]+)")
_WRAPPER = re.compile(r"^(?:\w+\()+")
_TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")

# protobuf field numbers: XSpace.planes; XPlane.name, .event_metadata,
# .stat_metadata; XEventMetadata.name, .stats; XStat.metadata_id,
# .bytes_value; XStatMetadata.id, .name; HloProto.hlo_module;
# HloModuleProto.computations; HloComputationProto.instructions;
# HloInstructionProto.name, .metadata; OpMetadata.op_name
_PLANES, _P_NAME, _P_EVENT_META, _P_STAT_META = 1, 2, 4, 5
_EM_NAME, _EM_STATS = 2, 5
_ST_META_ID, _ST_BYTES = 1, 6
_SM_ID, _SM_NAME = 1, 2
_HLO_MODULE, _COMPUTATIONS, _INSTRUCTIONS = 1, 3, 2
_I_NAME, _I_METADATA, _OP_NAME = 1, 7, 2


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    bytes for length-delimited fields, None for fixed-width ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            val, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, val


def _first(buf, num, default=b""):
    return next((v for k, v in _fields(buf) if k == num), default)


def hlo_op_names(data: bytes) -> dict:
    """{module name: {instruction name: op_name}} from the HLO protos a
    serialized XSpace carries on its ``/host:metadata`` plane."""
    out = {}
    for num, plane in _fields(data):
        if num != _PLANES or _first(plane, _P_NAME) != b"/host:metadata":
            continue
        hlo_stat = None
        for k, v in _fields(plane):
            if k == _P_STAT_META:
                meta = _first(v, 2)  # map entry: key 1, value 2
                if _first(meta, _SM_NAME) == b"Hlo Proto":
                    hlo_stat = _first(meta, _SM_ID, None)
        for k, v in _fields(plane):
            if k != _P_EVENT_META:
                continue
            meta = _first(v, 2)
            name = _first(meta, _EM_NAME).decode()
            for sk, stat in _fields(meta):
                if (sk == _EM_STATS
                        and _first(stat, _ST_META_ID, None) == hlo_stat):
                    out[name] = _instruction_op_names(
                        _first(_first(stat, _ST_BYTES), _HLO_MODULE))
    return out


def _instruction_op_names(module: bytes) -> dict:
    names = {}
    for k, comp in _fields(module):
        if k != _COMPUTATIONS:
            continue
        for ck, instr in _fields(comp):
            if ck == _INSTRUCTIONS:
                op_name = _first(_first(instr, _I_METADATA), _OP_NAME)
                if op_name:
                    names[_first(instr, _I_NAME).decode()] = op_name.decode()
    return names


def scope_of(op_name) -> str:
    """The first component of an op's name stack that is a scope."""
    for part in (op_name or "").split("/"):
        if "jit(" in part:
            continue
        inner = _WRAPPER.sub("", part).rstrip(")")
        if inner in SCOPES:
            return inner
    return NONE


def _module_at(modules, t):
    """The name of the module event (sorted (start, end, name)) around
    time ``t``, or None."""
    i = bisect.bisect_right(modules, (t, float("inf"))) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return None


def assign_scopes(events, op_name_of):
    """([(start_ns, end_ns, name, scope)], found) for one device's ops
    ``events`` [(start_ns, end_ns, name)]: ``op_name_of(start_ns,
    instruction)`` gives an op's name stack, or None where it has none of
    its own; such an op takes the scope of the innermost container op
    around it. ``found`` counts the ops with a name stack."""
    ops, around, found = [], [], 0  # around: (end, scope) of open containers
    instr = {}
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while around and around[-1][0] <= s:
            around.pop()
        if name not in instr:
            m = _INSTR.match(name)
            instr[name] = (m.group(1) if m else name,
                           trace.is_container(name))
        op_name = op_name_of(s, instr[name][0])
        if op_name is None:
            scope = around[-1][1] if around else NONE
        else:
            found += 1
            scope = scope_of(op_name)
        ops.append((s, e, name, scope))
        if instr[name][1]:
            around.append((e, scope))
    return ops, found


def extract(path: str, *, device_prefix: str = "/device:TPU:"):
    """(devices {plane: [(start_ns, end_ns, name, scope)]}, spans
    [(start_ns, end_ns, name, args)], scoped) from one ``.xplane.pb``;
    ``scoped`` counts the device ops with a name stack of their own."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    pd = ProfileData.from_serialized_xspace(data)
    names = hlo_op_names(data)
    merged = {}
    for table in names.values():
        merged.update(table)
    devices, spans, scoped = {}, [], 0
    for plane in pd.planes:
        if not plane.name.startswith(device_prefix):
            for line in plane.lines:
                spans.extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name,
                     dict(e.stats))
                    for e in line.events if e.name.startswith(SPAN_PREFIXES))
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in lines.get("XLA Modules", ()))
        ops, found = assign_scopes(
            [(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for e in lines.get("XLA Ops", ())],
            lambda t, instr: names.get(_module_at(modules, t),
                                       merged).get(instr))
        scoped += found
        devices[plane.name] = ops
    return devices, spans, scoped


def _label(name: str) -> str:
    """``sweep_run`` for ``chipbench.sweep_run``, ``sweep.masks`` for
    ``repro.sweep.masks``."""
    return name.split(".", 1)[1]


def reduce(devices: dict, spans: list, window) -> dict:
    """scope_s {scope: s}, busy_s (mean over devices), window_s,
    host_spans {name: [total_s, count]}, device_scopes [[scope, s]] and
    idle_gaps [[span, s]] (the 10 longest) of one window."""
    lo, hi = window
    if hi <= lo:
        raise ValueError("empty trace window")
    container = functools.lru_cache(maxsize=None)(trace.is_container)
    scope_s = {}
    for ops in devices.values():
        cursor = lo
        for s, e, name, scope in sorted(ops):
            if container(name):
                continue
            s, e = max(s, cursor), min(e, hi)
            if e > s:
                scope_s[scope] = scope_s.get(scope, 0.0) + (e - s) * 1e-9
                cursor = e
    host = {}
    for s, e, name, _ in spans:
        if s < hi and e > lo:
            tot = host.setdefault(name, [0.0, 0])
            tot[0] += (e - s) * 1e-9
            tot[1] += 1
    gaps = []
    first = devices[sorted(devices)[0]] if devices else []
    leaf = [op for op in first if not container(op[2])]
    cursor = lo
    for s, e in trace.merge(leaf, lo, hi) + [[hi, hi]]:
        if s > cursor:
            mid = 0.5 * (cursor + s)
            open_ = [(a, n) for a, b, n, _ in spans if a <= mid < b]
            label = _label(max(open_)[1]) if open_ else "between_calls"
            gaps.append([label, (s - cursor) * 1e-9])
        cursor = max(cursor, e)
    gaps.sort(key=lambda g: -g[1])
    return {
        "scope_s": scope_s,
        "busy_s": sum(scope_s.values()) / max(len(devices), 1),
        "window_s": (hi - lo) * 1e-9,
        "host_spans": host,
        "device_scopes": sorted(([k, v] for k, v in scope_s.items()),
                                key=lambda kv: -kv[1]),
        "idle_gaps": gaps[:10],
    }


def measure(ctx: dict) -> dict:
    """The program trace of this run, made once and kept in ``ctx``.

    Returns a dict with the ``reduce`` numbers and ``calls`` (grid calls
    profiled), ``rounds``, ``scoped`` (device ops with a name stack of
    their own), ``trace_lower_s``, ``recompiled`` and ``seconds`` (all the
    stretch took); or with ``error`` alone where it could not be made."""
    if "program_trace" not in ctx:
        try:
            ctx["program_trace"] = _measure(ctx)
        except Exception as e:  # a reader then finds nothing to read
            ctx["log"].write(f"program trace failed: {e!r}\n")
            ctx["program_trace"] = {"error": repr(e)}
    return ctx["program_trace"]


def span_ms_per_call(ctx: dict, name: str):
    """Milliseconds of the host span ``name`` per profiled grid call."""
    pt = measure(ctx)
    total = pt.get("host_spans", {}).get(name)
    return 1e3 * total[0] / pt["calls"] if total else None


def scope_us_per_round(ctx: dict, scopes):
    """Device microseconds under ``scopes`` per profiled grid round."""
    pt = measure(ctx)
    scope_s = pt.get("scope_s", {})
    if not any(s in scope_s for s in scopes):
        return None
    return (1e6 * sum(scope_s.get(s, 0.0) for s in scopes)
            / (pt["calls"] * pt["rounds"]))


def _program_scopes() -> tuple:
    """The scopes the program under test defines (none before it had any)."""
    try:
        from repro.obs import profile
    except ImportError:
        return ()
    return tuple(getattr(profile, "SCOPES", ()))


def _profile(cell, first_call: int, min_calls: int, min_seconds: float):
    """Profile grid calls from ``first_call`` on; (devices, spans, scoped,
    calls) of the trace."""
    import jax

    with tempfile.TemporaryDirectory(prefix="chipbench-scopes-") as d:
        # no Python function tracing: it would slow the host path the
        # spans time; the HLO protos stay in
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            n, t0 = 0, time.perf_counter()
            while n < min_calls or time.perf_counter() - t0 < min_seconds:
                cell.grid_call(first_call + n)
                n += 1
        finally:
            jax.profiler.stop_trace()
        return extract(trace.find_xplane(d)) + (n,)


def _relowered_call(cell, call: int):
    """One grid call after the in-memory caches are dropped: the executor
    is traced and lowered again. Returns the seconds of trace + lowering
    events, or None where JAX reported none."""
    import jax

    seconds = []

    def listen(event, duration, **kw):
        if event in _TRACE_EVENTS:
            seconds.append(duration)

    jax.clear_caches()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        cell.grid_call(call)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    return sum(seconds) or None


def _measure(ctx: dict, *, min_calls: int = 2, min_seconds: float = 1.0):
    import jax

    t_start = time.perf_counter()
    cell, log = ctx["cell"], ctx["log"]
    call = 1 + len(ctx["calls"]) + len(ctx.get("traced_calls") or ())
    trace_lower_s = _relowered_call(cell, call)
    devices, spans, scoped, n = _profile(cell, call + 1, min_calls,
                                         min_seconds)
    fresh = False
    if _program_scopes() and all(op[3] == NONE for ops in devices.values()
                                 for op in ops):
        # the program scopes its ops but the executable read from the
        # persistent cache carries another build's metadata: compile it
        # again with metadata in the cache key
        flag = "jax_compilation_cache_include_metadata_in_key"
        before = getattr(jax.config, flag)
        jax.config.update(flag, True)
        try:
            _relowered_call(cell, call + n + 1)
            devices, spans, scoped, n = _profile(cell, call + n + 2,
                                                 min_calls, min_seconds)
        finally:
            jax.config.update(flag, before)
        fresh = True
    runs = [s for s in spans if s[2] == trace.SPAN_PREFIX + "sweep_run"]
    fetches = [s for s in spans if s[2] == trace.SPAN_PREFIX + "fetch"]
    out = reduce(devices, spans, (min(s[0] for s in runs),
                                  max(s[1] for s in fetches)))
    out.update(calls=n, rounds=cell.rounds, scoped=scoped,
               trace_lower_s=trace_lower_s, recompiled=fresh)
    top, base = {}, {}
    for ops in devices.values():
        for s, e, name, scope in ops:
            if name not in base:
                base[name] = (None if trace.is_container(name)
                              else trace.base_name(name))
            if base[name] is not None:
                key = (scope, base[name])
                top[key] = top.get(key, 0.0) + (e - s) * 1e-9
    out["seconds"] = time.perf_counter() - t_start
    log.write(f"program trace: {out['seconds']:.3f} s in all; trace + "
              f"lowering {trace_lower_s} s; {n} grid calls profiled, "
              f"compiled again with metadata in the key: {fresh}; {scoped} "
              f"of {sum(map(len, devices.values()))} device ops with a "
              f"name stack\n")
    log.write("program trace breakdown: " + json.dumps(
        {k: out[k] for k in ("device_scopes", "idle_gaps", "host_spans",
                             "busy_s", "window_s")}) + "\n")
    log.write("program trace top ops: " + json.dumps(
        [[f"{sc}:{op}", t] for (sc, op), t in
         sorted(top.items(), key=lambda kv: -kv[1])[:12]]) + "\n")
    return out
