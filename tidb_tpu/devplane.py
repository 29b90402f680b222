"""One device plane: the process-wide 1-D ``("batch",)`` mesh.

Single-chip and multi-chip execution share one layout language: rows are
``NamedSharding(mesh, PartitionSpec("batch"))`` (each chip holds a
contiguous row shard of the padded superchunk) and small/broadcast state
is ``PartitionSpec()`` (replicated). Every kernel — the fused copTask
agg, the mesh group-agg, the lookup join, the shuffle join — addresses
devices only through these two specs plus the ``"batch"`` axis name, so
the same compiled program drives 1 device and N devices; on one device
the collectives (psum-style merges, all_gather, all_to_all) are elided
at trace time by the ``ndev == 1`` guards and the program lowers to the
plain single-chip kernel. Under ``JAX_PLATFORMS=cpu`` a mesh of virtual
host devices behaves identically (the t5x pjit-on-cpu posture: jit IS
pjit, so no separate fallback wrapper is needed — ``plane_jit`` exists
as the one seam where that would change).

The mesh is a process property, like the reference's store topology
(store/tikv/coprocessor.go fan-out): one plane serves every session.
The planner consults ``active_mesh()`` to route plans, and bumps
``mesh_generation()`` into the plan-cache key so cached plans never
outlive a topology change; ``mesh_fingerprint()`` is the analogous
identity folded into kernel-cache and persistent compile-cache keys so
a 1-chip and an 8-chip executable for the same plan can never collide.

Concurrency: configuration happens at process start / test setup, on
one thread; readers (`active_mesh`, `mesh_generation`, `ndev`) see a
single attribute load each (atomic under the GIL), so no lock is
needed — the generation counter is the coherence protocol.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from jax import shard_map as _shard_map_fn

__all__ = [
    "AXIS", "build_mesh", "configure_mesh", "enable_mesh", "disable_mesh",
    "active_mesh", "mesh_generation", "on_topology_change", "ndev",
    "batch_spec", "replicated_spec", "batch_sharding", "replicated",
    "chip_device", "chip_scope", "mesh_fingerprint", "shard_map",
    "pmax", "named", "plane_jit",
]

#: the one data-parallel axis name of the device plane
AXIS = "batch"

_mesh: Mesh | None = None
_generation = 0
_listeners: list = []


# -- construction ----------------------------------------------------------

def build_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """A 1-D ``("batch",)`` mesh over the first n_devices jax devices.
    Asking for more devices than are visible raises: a plane silently
    smaller than the one requested would run, and be measured, as if
    it were the requested one."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise RuntimeError(
                f"mesh of {n_devices} devices requested but only "
                f"{len(devices)} visible "
                f"(platform {devices[0].platform})")
        devices = devices[:n_devices]
    return Mesh(np.array(devices), axis_names=(AXIS,))


# -- process configuration -------------------------------------------------

def on_topology_change(fn) -> None:
    """Register fn() to run after every mesh (re)configuration — kernel
    caches keyed on the generation use this to release compiled programs
    that can never be hit again (e.g. after disable_mesh)."""
    _listeners.append(fn)


def configure_mesh(mesh) -> None:
    """Install `mesh` (a jax.sharding.Mesh or None) as the process mesh."""
    global _mesh, _generation
    _mesh = mesh
    _generation += 1
    for fn in _listeners:
        fn()


def enable_mesh(n_devices: int | None = None) -> None:
    """Build a ``("batch",)`` mesh over the first n jax devices and
    install it."""
    configure_mesh(build_mesh(n_devices))


def disable_mesh() -> None:
    configure_mesh(None)


def active_mesh() -> Mesh | None:
    return _mesh


def mesh_generation() -> int:
    return _generation


def ndev(mesh: Mesh | None = None) -> int:
    """Device count of `mesh` (default: the process mesh; 1 if none)."""
    if mesh is None:
        mesh = _mesh
    return 1 if mesh is None else int(mesh.devices.size)


# -- layout language -------------------------------------------------------

def batch_spec() -> PartitionSpec:
    """Rows sharded over the ``"batch"`` axis."""
    return PartitionSpec(AXIS)


def replicated_spec() -> PartitionSpec:
    return PartitionSpec()


def batch_sharding(mesh: Mesh | None = None) -> NamedSharding:
    """``NamedSharding(mesh, P("batch"))`` — superchunk row layout."""
    return NamedSharding(_mesh if mesh is None else mesh, batch_spec())


def replicated(mesh: Mesh | None = None) -> NamedSharding:
    """``NamedSharding(mesh, P())`` — broadcast state / HBM point blocks."""
    return NamedSharding(_mesh if mesh is None else mesh, replicated_spec())


def chip_device(chip: int, mesh: Mesh | None = None):
    """The jax device backing plane chip index `chip` (modulo the
    device count); None when no mesh is installed — callers then use
    the default device."""
    if mesh is None:
        mesh = _mesh
    if mesh is None:
        return None
    return mesh.devices.flat[chip % int(mesh.devices.size)]


def chip_scope(chip: int, mesh: Mesh | None = None):
    """Place a slot-guarded dispatch section's UNCOMMITTED transfers
    and jit executions on chip `chip`'s device (jax.default_device).
    Committed inputs — replicated HBM blocks, sharded superchunks —
    keep their NamedSharding placement regardless; this steers only the
    host-staged point/one-shot dispatches the scheduler just placed.
    No-op without a mesh."""
    dev = chip_device(chip, mesh)
    if dev is None:
        return contextlib.nullcontext()
    return jax.default_device(dev)


def mesh_fingerprint(mesh: Mesh | None = None, *,
                     process: bool = False) -> tuple:
    """Structural identity of the plane for cache keys: axis layout +
    device count + platform. Two executables compiled under different
    fingerprints never alias. With ``process=True``, fingerprint the
    installed process mesh (the common case for kernel caches keyed
    before a mesh is chosen per dispatch)."""
    if mesh is None and process:
        mesh = _mesh
    if mesh is None:
        return ("host", 1)
    plat = mesh.devices.flat[0].platform
    return (AXIS, int(mesh.devices.size), plat)


# -- compiled-program seams ------------------------------------------------

def shard_map(fn, mesh: Mesh, in_specs, out_specs):
    """shard_map with replication checking off (our kernels mix manually
    replicated scalars with sharded lanes)."""
    return _shard_map_fn(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def pmax(x, axes=(AXIS,)):
    """Cross-chip maximum of `x` over `axes`, as all_gather + a local
    max. Every lane of the plane is int64, which the TPU emulates, and
    XLA:TPU lowers only the SUM all-reduce for emulated types
    ("UNIMPLEMENTED: Supported lowering only of Sum all reduce" — what
    `lax.pmax` on an s64 scalar answered on four v5e chips); all_gather
    moves bytes and lowers for any type."""
    return jax.numpy.max(jax.lax.all_gather(x, axes), axis=0)


def named(fn, family: str):
    """`fn` under the name of its kernel family, for `jax.jit`: the XLA
    module is called `jit_<fn.__name__>`, and every kernel class stages
    a method called `_kernel`, so without this a device trace shows
    `jit__kernel` for every family. The name is also what the compile
    caches see. A wrapper, not a rename: bound methods refuse a new
    `__name__`; it runs at trace time only (the jitted call's fast path
    never re-enters Python)."""
    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = family
    return program


def plane_jit(fn, name: str, **kwargs):
    """jit for plane kernels. Modern jax's jit IS pjit — NamedSharding
    inputs drive partitioned compilation directly, and on cpu a
    virtual-device mesh lowers the same way — so this is a plain jit
    today; it exists as the single seam to grow per-backend dispatch
    options (donation policies, compiler flags) without touching every
    kernel. Each wrap registers one `plane`-family compile unit with
    the kernel-profile registry (keyed by the staged function's name +
    the process mesh): plane-stage re-jitting that the executable
    caches should have absorbed shows up as compile churn on one row.
    `name` is the program's family name on the device trace (`named`)."""
    from tidb_tpu import profiler
    prof = profiler.profile("plane", getattr(fn, "__name__", "shard"))
    profiler.note_construct(prof, reuse=False)
    return jax.jit(named(fn, name), **kwargs)
