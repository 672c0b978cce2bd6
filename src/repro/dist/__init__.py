"""Distributed sweep subsystem: the experiment grid on a device mesh.

FedChain's Tables 1-4 grids are embarrassingly parallel twice over: across
the problems x seeds x stepsizes cells of a sweep (independent runs joined by
nothing), and across the N clients inside a round (independent local
computations joined only by the server aggregation). This package maps both
onto a JAX device mesh with ``shard_map``, on top of the single-compile
executors of ``core.runner``/``core.chain``/``core.sweep``:

Mesh layout
-----------
``dist.mesh`` builds 1-D ``('grid',)`` meshes (and 2-D ``('grid', 'client')``
ones). The two axes carry the two parallelisms:

* **grid axis** (``dist.grid``) -- the flattened problems x seeds cells of a
  sweep are partitioned across the ``grid`` mesh axis. ``dist.partition``
  flattens cell (p, s) to index ``p * n_seeds + s``, pads the flat axis up to
  a multiple of the axis size by REPEATING real cells, and keeps the inverse
  map; padding cells compute and are dropped on the way out, so the
  unpadded results are a bijection onto the vmapped grid (property-tested).
  Every per-cell operand -- stacked ``ProblemSpec`` leaves, per-cell x0,
  per-cell PRNG keys (the same ``PRNGKey(seed)`` / mask-schedule fold
  ``p * n_seeds + s`` the single-device sweep uses), comm mask schedules --
  is placed on its shard through the ``cells`` logical axis of
  ``sharding.rules``; the stepsize axis stays dense inside each cell.
  Inside each shard the SAME cell functions as ``core.sweep`` run under the
  same vmap nesting, so the sharded grid is **bitwise identical** to the
  single-device ``run_sweep`` (tested on a CPU debug mesh built with
  ``--xla_force_host_platform_device_count``).

* **client axis** (``dist.client_axis``) -- inside one cell, the ``[N, ...]``
  client dimension is sharded: each device runs its clients' local
  computations and the Pallas ``chain_aggregate`` /
  ``weighted_mean_over_clients`` kernels on its LOCAL rows, and one
  cross-device ``jax.lax.psum`` over the ``client`` axis completes the
  client mean -- the grouped-collective structure of the paper's local
  phase (a per-client computation joined only by aggregation). Summing
  per-shard partial aggregates reorders the float reduction, so this axis
  is equivalent-to-tolerance rather than bitwise; the grid axis is the
  bitwise (and production) path.

Why bits accounting is placement-invariant
------------------------------------------
``bits_up``/``bits_down`` are computed INSIDE each cell's scan from the
round's participation mask and the closed-form per-client costs
(``repro.comm``) -- they are functions of schedule data that rides the cell's
shard, never of device placement. Sharding the grid axis moves whole cells
(each carries its own mask schedule, derived from the same per-cell fold as
the single-device path); sharding the client axis moves rows of a mean whose
billed size is a static shape. Either way the accounted wire cost is
identical to the single-device run -- asserted bit-for-bit in the dist tests.

Single-compile discipline survives sharding: the shard_map body is traced
once per executor structure (``runner.TRACE_COUNTS`` moves by exactly one),
problems / comm knobs / schedules stay operands, and re-running any
same-shaped grid on the same mesh reuses the compile.

Entry points: ``core.sweep.run_sweep(..., mesh=...)`` and
``core.sweep.run_fraction_sweep(..., mesh=...)`` delegate here;
``dist.grid.run_sweep_sharded`` / ``run_fraction_sweep_sharded`` are the
direct API.
"""
from repro.dist import mesh, partition  # noqa: F401
from repro.dist.mesh import (  # noqa: F401
    auto_grid_mesh,
    client_size,
    grid_size,
    make_grid_client_mesh,
    make_grid_mesh,
    mesh_signature,
)
