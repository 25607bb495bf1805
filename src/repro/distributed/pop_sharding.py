"""Population-axis sharding for MOHAQ candidate evaluation.

The GA search scores whole populations per generation through a
``SearchTarget``'s population forward (``repro.core.api``; e.g.
``models.sru.forward_population`` or the xLSTM target's vmapped lane) — a
(P, ...) batch whose lanes are completely independent (one quantization
candidate per lane, no cross-lane reduction anywhere in the forward or
the error count). Nothing here is model-specific: any lane-independent
``fn(*replicated, batched)`` partitions the same way. That independence
makes the population axis trivially data-parallel: partition P across a
1-D device mesh, replicate everything else (parameters, the precomputed
quantized-weight banks, validation features/labels, and the
calibration-derived quantization grids baked into ``qp_stack`` rows), and
gather the per-candidate integer error counts back to the host.

Quantized-weight banks shard like parameters: the (|menu|, m, h) stacks
replicate to every device and each shard gathers its local lanes' rows
(``jnp.take`` by menu index) inside its own program — the gather is
per-lane, so replicated-bank + sharded-index is exactly the single-device
gather restricted to the shard's lanes, and the bit-identical-front
contract (tests/test_sharded_eval.py) carries over unchanged.

Two partitioned lowerings are provided:

- ``shard_map`` (default): each device runs the *exact* single-device
  program on its local (P/n, ...) slice — per-lane arithmetic is identical
  by construction, so the bit-identical-Pareto-front contract of the
  batched evaluator (PRs 1-2) extends to the mesh without any tolerance.
- ``gspmd``: plain ``jit`` with ``in_shardings``/``out_shardings``
  PartitionSpecs; the partitioner propagates the population axis from the
  sharded ``qp_stack`` input (helped by the ``pop`` logical-axis
  constraints inside ``forward_population``). Kept as the path real-TPU
  deployments would use (XLA can overlap gather/compute); parity is
  asserted by tests, not by construction.

Uneven populations: candidate counts are padded up to a multiple of the
mesh's population-axis size (duplicating the last row — padding lanes are
sliced off after the gather, so their values never matter), on top of the
compile-size bucketing ``core.batched_eval`` already does.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

POP_AXIS = "pop"

PARTITION_MODES = ("shard_map", "gspmd")


def pop_axis_size(mesh: Optional[Mesh], axis: str = POP_AXIS) -> int:
    """Number of population shards a mesh provides (1 without a mesh)."""
    if mesh is None:
        return 1
    if axis not in mesh.shape:
        raise ValueError(f"mesh {mesh.axis_names} has no {axis!r} axis")
    return int(mesh.shape[axis])


def padded_pop(bucket: int, n_shards: int) -> int:
    """Population padding target: the compile bucket rounded up to a
    multiple of the mesh's population-axis size (every shard gets the same
    lane count — jit sharding requires even partitions)."""
    return -(-bucket // n_shards) * n_shards


def shrink_mesh(mesh: Mesh, keep: int, axis: str = POP_AXIS) -> Mesh:
    """The surviving mesh after a (simulated) device loss: the first
    ``keep`` devices of the population axis, same axis name. Used by the
    evaluator's graceful-degradation path — populations re-pad to the new
    shard count and lanes stay independent, so re-dispatching on the
    shrunk mesh reproduces every real lane's error count exactly."""
    n = pop_axis_size(mesh, axis)
    if not 0 < keep < n:
        raise ValueError(f"keep={keep} must shrink the {n}-shard mesh")
    survivors = mesh.devices.reshape(-1)[:keep]
    return Mesh(survivors.reshape(keep), (axis,))


def shard_population(fn: Callable, mesh: Mesh, *, n_replicated: int,
                     axis: str = POP_AXIS, mode: str = "shard_map"):
    """Partition ``fn(*replicated_args, batched_arg)`` over the population
    axis of its LAST argument and return a jitted callable with the same
    global-shape signature.

    ``fn`` must be lane-independent in its last argument's leading axis
    (true of the population evaluator: one candidate per lane) and is
    called with ``n_replicated`` leading replicated arguments.
    ``mode="shard_map"`` runs the exact per-shard program;
    ``mode="gspmd"`` lets the SPMD partitioner lower the global program
    from in/out PartitionSpecs.
    """
    if mode not in PARTITION_MODES:
        raise ValueError(f"mode must be one of {PARTITION_MODES}: {mode!r}")
    pop_axis_size(mesh, axis)          # validates the axis exists
    rep_specs = (P(),) * n_replicated
    if mode == "shard_map":
        inner = jax.shard_map(fn, mesh=mesh,
                              in_specs=rep_specs + (P(axis),),
                              out_specs=P(axis), check_vma=False)
        return jax.jit(inner)
    rep = NamedSharding(mesh, P())
    return jax.jit(fn, in_shardings=(rep,) * n_replicated
                   + (NamedSharding(mesh, P(axis)),),
                   out_shardings=rep)


def gather_counts(counts) -> "jax.Array":
    """Gather per-candidate error counts to a fully-addressable host value.

    With ``shard_map``/``gspmd`` outputs the result is already a global
    array; this just blocks and devices-get so callers can slice the
    padding lanes off in numpy."""
    return jax.device_get(counts)
