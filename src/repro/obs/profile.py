"""Layer 3: the program's host spans and round-body scopes, run manifests,
and a uniform compile-vs-warm phase capture.

* ``annotate(name, **args)``: a ``jax.profiler.TraceAnnotation`` span on
  the host (free while no profiler runs). The sweep host path opens
  ``repro.sweep.*`` spans with it; ``benchmarks/run.py --profile`` wraps
  its harness phases.
* ``scope(name)``: a ``jax.named_scope`` over one part of the round body,
  named from the fixed vocabulary ``SCOPES``. A scope only changes the op
  metadata (``op_name``) of what it covers, never the computation, so a
  profiler trace can give every device op its part of the round.
* ``phase``/``write_manifest``: benchmark phases as obs ``phase`` events and
  the run environment next to every ``BENCH_*.json``.

Spans and manifests are host-side; scopes run at trace time and leave no
other trace behind.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import time

import jax
from jax.profiler import TraceAnnotation

MANIFEST_PATH = "BENCH_manifest.json"


def run_manifest(extra: dict = None) -> dict:
    """The run environment a benchmark number depends on, as a flat dict
    with a stable ``config_hash`` over the sorted contents."""
    manifest = {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "devices": [str(d) for d in jax.devices()],
        "jax_version": jax.__version__,
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "force_pallas": os.environ.get("REPRO_FORCE_PALLAS", ""),
    }
    if extra:
        manifest.update(extra)
    digest = hashlib.sha256(
        json.dumps(manifest, sort_keys=True).encode()).hexdigest()
    manifest["config_hash"] = digest[:16]
    return manifest


def write_manifest(path: str = MANIFEST_PATH, extra: dict = None) -> dict:
    manifest = run_manifest(extra)
    with open(path, "w") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
        f.write("\n")
    return manifest


# the parts of a round a device op can belong to (``scope``); a trace
# reader gives each op the first of these in its name stack, or none
SCOPES = ("sample", "local", "downlink", "uplink", "server", "select",
          "eval", "taps")


def annotate(name: str, **args):
    """A named host span in a captured profiler trace, with ``args`` as
    its arguments; costs a flag check while no profiler runs."""
    return TraceAnnotation(name, **args)


def scope(name: str):
    """A ``jax.named_scope`` over one part of the round body: use as
    ``with scope("local"):`` or as a decorator. ``name`` must be one of
    ``SCOPES``."""
    if name not in SCOPES:
        raise ValueError(f"unknown round-body scope {name!r}; the "
                         f"vocabulary is {SCOPES}")
    return jax.named_scope(name)


@contextlib.contextmanager
def phase(name: str):
    """Measure one benchmark phase: wall seconds + TRACE_COUNTS movement.

    Yields a dict filled at exit with ``seconds``, ``traces`` (total trace
    count the phase paid) and ``trace_tags``; the same summary is emitted as
    a ``phase`` event to the installed obs recorder. Wrapping a harness call
    twice — cold then warm — is the uniform compile-vs-warm breakdown
    ``benchmarks/run.py --profile`` reports: the cold phase carries the
    compiles, the warm phase must carry none.
    """
    from repro.core import runner
    from repro.obs import events

    info = {"name": name}
    before = dict(runner.TRACE_COUNTS)
    t0 = time.perf_counter()
    with annotate(name):
        yield info
    info["seconds"] = round(time.perf_counter() - t0, 6)
    deltas = runner.trace_deltas(before)
    info["traces"] = sum(deltas.values())
    info["trace_tags"] = sorted(deltas)
    events.emit("phase", **info)
