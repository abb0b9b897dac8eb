"""Mesh-sharded execution backend — the OMP2MPI leap (ISSUE 9).

The paper's sibling tool OMP2MPI (arXiv:1502.02921) generated
*distributed* programs from the same pragma source OMP2HMPP compiled for
one accelerator.  This module is that leap for the plan runtime: a
``Backend`` whose ``AdvancedLoad``/``DelegateStore`` lower to **sharded**
uploads over a device mesh, so the same ``Plan`` that drove one GPU
drives an SPMD group — GSPMD inserts the collective schedule when the
jitted block bodies consume sharded operands.

Three pieces:

``MeshBackend``
    A ``JaxDeviceBackend`` over a ``jax.sharding.Mesh`` of every visible
    device (shape auto-derived, e.g. 8 devices → ``(2, 4)`` over
    ``("data", "model")``).  ``upload(host, name=...)`` places the array
    with ``NamedSharding(mesh, PartitionSpec(*placement[name]))`` — the
    per-variable placement the tuner chose; unmapped variables
    replicate.  ``with_placement`` returns a memoized twin per placement
    (jit caches shared per twin), and ``variant`` twins preserve the
    mesh + placement.

``placement_specs``
    Turns one placement *policy* (``replicate`` / ``fsdp`` / ``tp``)
    into per-variable ``PartitionSpec`` entries through
    ``distributed.sharding``'s divisibility-guarded logical-axis rules —
    fsdp shards dim 0 over "data" (logical ``embed``), tp shards the
    last dim over "model" (logical ``ffn``); non-dividing dims stay
    replicated with the drop recorded, so every spec is jit-valid.

``mesh_cost_terms``
    Prices a placement for the tuner without running it: lowers each
    offload block with ``in_shardings`` and reads per-device dot FLOPs
    and collective ring-volume bytes straight off the compiled (post-
    SPMD) HLO, plus a per-variable h2d factor (a replicated upload
    copies to every device; a sharded one moves each byte once).

The tuner crosses these placements with its existing policy × streams ×
fusion × donation grid (``PlanConfig.mesh_placement``), prices the
collectives against ``ici_bw`` (``roofline.analysis.offload_cost_terms``)
and records the winning placement in ``plan.meta["mesh"]`` — which
``execute()`` re-applies on any placement-capable backend.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core.backend import Event, JaxDeviceBackend, register_backend
from repro.distributed.sharding import make_rules, spec_for_axes

__all__ = [
    "MeshBackend", "DEFAULT_PLACEMENTS", "auto_mesh_shape",
    "canonical_placement", "placement_specs", "mesh_cost_terms",
]

# the tuner's placement axis: replicate everywhere / FSDP-shard dim 0
# over "data" / TP-shard the last dim over "model"
DEFAULT_PLACEMENTS = ("replicate", "fsdp", "tp")


def auto_mesh_shape(n_devices: int,
                    axes: Tuple[str, str] = ("data", "model")
                    ) -> Tuple[int, int]:
    """(data, model) shape for ``n_devices``, as square as it divides:
    data = the largest divisor whose square is at most ``n_devices``,
    model = the rest.  8 → (2, 4); 4 → (2, 2), the chips of a v5e host's
    2x2 topology, so that both fsdp and tp shard; 1 → (1, 1)."""
    data = max(d for d in range(1, n_devices + 1)
               if n_devices % d == 0 and d * d <= n_devices)
    return (data, n_devices // data)


def canonical_placement(placement: Any) -> Tuple[Tuple[str, tuple], ...]:
    """Normalize a placement (dict / item-iterable, entries possibly
    JSON-round-tripped lists) to a hashable, sorted
    ``((var, (entry, ...)), ...)`` tuple — the identity ``MeshBackend``
    memoizes twins and keys compiled-plan caches on."""
    if not placement:
        return ()
    items = placement.items() if hasattr(placement, "items") else placement
    out = []
    for var, entries in sorted(items):
        ent = tuple(tuple(e) if isinstance(e, (list, tuple)) else e
                    for e in (entries or ()))
        out.append((str(var), ent))
    return tuple(out)


class MeshBackend(JaxDeviceBackend):
    """JAX SPMD backend over a device mesh with per-variable placements."""

    name = "mesh"

    def __init__(self, device=None, *, mesh=None, shape=None,
                 axes: Tuple[str, ...] = ("data", "model"),
                 n_streams: int = 2, donate: bool = True,
                 placement: Any = ()):
        super().__init__(device, n_streams=n_streams, donate=donate)
        from jax.sharding import Mesh
        if mesh is None:
            devs = self._jax.devices()
            if shape is None:
                shape = auto_mesh_shape(len(devs), tuple(axes))
            n = int(np.prod(shape))
            mesh = Mesh(np.asarray(devs[:n]).reshape(shape), tuple(axes))
        self.mesh = mesh
        key = canonical_placement(placement)
        self.placement: Dict[str, tuple] = dict(key)
        self.placement_key = key
        # (placement_key, n_streams, donate) -> twin; shared by the whole
        # family so with_placement of a variant of a twin never rebuilds
        self._placement_twins: Dict[Any, "MeshBackend"] = {
            (key, n_streams, donate): self}

    # -- identity ----------------------------------------------------------
    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def mesh_desc(self) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
        return (tuple(self.mesh.devices.shape), tuple(self.mesh.axis_names))

    @property
    def mesh_key(self) -> str:
        """Mesh identity for tunecache fingerprints (shape + axes only:
        the placement is a per-candidate knob, fingerprinted through the
        tuning grid, not a property of the backend's device pool)."""
        shape, axes = self.mesh_desc
        return "x".join(f"{a}{s}" for a, s in zip(axes, shape))

    # -- twins -------------------------------------------------------------
    def variant(self, *, n_streams: Optional[int] = None,
                donate: Optional[bool] = None) -> "MeshBackend":
        ns = self.n_streams if n_streams is None else max(1, int(n_streams))
        dn = self.donate if donate is None else bool(donate)
        twin = self._variant_pool.get((ns, dn))
        if twin is None:
            twin = MeshBackend(device=self._device, mesh=self.mesh,
                               n_streams=ns, donate=dn,
                               placement=self.placement_key)
            twin._variant_pool = self._variant_pool
            twin._placement_twins = self._placement_twins
            self._variant_pool[(ns, dn)] = twin
            self._placement_twins.setdefault(
                (self.placement_key, ns, dn), twin)
        return twin

    def with_placement(self, placement: Any) -> "MeshBackend":
        """Twin with the given per-variable placement (memoized: same
        placement → same instance → shared jit/lowering caches)."""
        key = canonical_placement(placement)
        if key == self.placement_key:
            return self
        pool_key = (key, self.n_streams, self.donate)
        twin = self._placement_twins.get(pool_key)
        if twin is None:
            twin = MeshBackend(device=self._device, mesh=self.mesh,
                               n_streams=self.n_streams, donate=self.donate,
                               placement=key)
            twin._placement_twins = self._placement_twins
            self._placement_twins[pool_key] = twin
        return twin

    # -- transfers ---------------------------------------------------------
    def _sharding_for(self, name: Optional[str]):
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh,
                             PartitionSpec(*self.placement.get(name, ())))

    def upload(self, host, *, stream: int = 0, name=None):
        handle = self._jax.device_put(host, self._sharding_for(name))
        self._record(stream, Event(payload=handle))
        return handle


register_backend("mesh", MeshBackend)


# ---------------------------------------------------------------------------
# Placement policies and pricing (tuner-facing, no backend state)
# ---------------------------------------------------------------------------

def _build_mesh(mesh_desc):
    from jax import devices
    from jax.sharding import Mesh
    shape, axes = mesh_desc
    n = int(np.prod(shape))
    return Mesh(np.asarray(devices()[:n]).reshape(shape), tuple(axes))


def placement_specs(shapes: Dict[str, Any], mesh, policy: str
                    ) -> Tuple[Dict[str, tuple], List[tuple]]:
    """Per-variable PartitionSpec entries for one placement policy.

    ``shapes`` maps var → anything with ``.shape`` (the planner's
    abstract values); ``mesh`` is a Mesh / AbstractMesh.  Returns
    ``(specs, dropped)``: specs as plain entry tuples (JSON-safe once
    listified), dropped as the divisibility-guard records — every entry
    that survives the guard is jit-valid by construction."""
    rules = make_rules(mesh, "train")
    specs: Dict[str, tuple] = {}
    for var in sorted(shapes):
        shape = tuple(np.shape(shapes[var]) if not hasattr(shapes[var],
                                                           "shape")
                      else shapes[var].shape)
        nd = len(shape)
        if policy == "replicate" or nd == 0:
            specs[var] = ()
            continue
        if policy == "fsdp":
            axes = ("embed",) + (None,) * (nd - 1)
        elif policy == "tp":
            axes = (None,) * (nd - 1) + ("ffn",)
        else:
            raise ValueError(f"unknown placement policy {policy!r}; have "
                             f"{DEFAULT_PLACEMENTS}")
        spec = spec_for_axes(rules, shape, axes, context=var)
        specs[var] = tuple(spec)
    return specs, list(rules.dropped)


def _shard_factor(mesh_shape: Dict[str, int], entries) -> int:
    """Number of distinct shards an entry tuple splits an array into."""
    s = 1
    for e in entries or ():
        if e is None:
            continue
        for a in (e if isinstance(e, (list, tuple)) else (e,)):
            s *= mesh_shape[a]
    return s


def mesh_cost_terms(program, shapes: Dict[str, Any], backend: MeshBackend,
                    specs: Dict[str, tuple]) -> Dict[str, Any]:
    """Price one placement for the tuner's cost model, without running it.

    Lowers every non-kernel offload block with ``in_shardings`` per
    ``specs`` and reads off the compiled per-device HLO:

    * ``flops_by_block``  — per-device dot FLOPs (GSPMD partitioned the
      dots, so a tp-sharded matmul reports 1/n of the math per chip);
    * ``coll_by_block``   — ring-volume wire bytes of the collectives
      GSPMD inserted (``roofline.analysis.collective_bytes``);
    * ``h2d_factor``      — per-variable PCIe multiplier: a replicated
      upload copies the host bytes to all n devices, a fully sharded one
      moves each byte once (n / shard_count in general).

    Kernel-tagged blocks keep their analytic per-variant roofline pricing
    (they are not sharded) and are skipped here."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.roofline.analysis import (collective_bytes, dot_flops,
                                         parse_hlo)
    mesh = backend.mesh
    n_dev = backend.n_devices
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    flops_by_block: Dict[int, float] = {}
    coll_by_block: Dict[int, float] = {}
    for blk in program.offload_blocks():
        if blk.kernel:
            continue
        reads = tuple(blk.reads)
        fn = blk.fn
        writes = tuple(blk.writes)

        def wrapped(*arrays, _fn=fn, _reads=reads, _writes=writes):
            out = _fn(jax.numpy, **dict(zip(_reads, arrays)))
            return tuple(out[w] for w in _writes)

        avals = [jax.ShapeDtypeStruct(shapes[v].shape, shapes[v].dtype)
                 for v in reads]
        in_sh = [NamedSharding(mesh, PartitionSpec(*specs.get(v, ())))
                 for v in reads]
        txt = (jax.jit(wrapped, in_shardings=in_sh)
               .lower(*avals).compile().as_text())
        mod = parse_hlo(txt)
        flops_by_block[blk.idx] = dot_flops(mod)
        coll_by_block[blk.idx] = sum(
            v["bytes"] for v in collective_bytes(mod).values())
    h2d_factor = {v: n_dev / _shard_factor(mesh_shape, e)
                  for v, e in specs.items()}
    return {
        "specs": specs,
        "flops_by_block": flops_by_block,
        "coll_by_block": coll_by_block,
        "h2d_factor": h2d_factor,
        "n_devices": n_dev,
    }
