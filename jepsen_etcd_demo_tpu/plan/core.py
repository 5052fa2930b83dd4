"""KernelPlan — the one plan object under every kernel launch.

A ``KernelPlan`` is the runtime face of one ``contracts.json`` kernel
family: geometry (DenseConfig / WGLConfig / tile shape), chunking,
batch shape, mesh + axis names, sparsity/dedup mode, the donation set
and carry fields the contract declares, and the provenance of each
choice. Plans are built by the routing planners in ``plan.dispatch``
(which own the policy that used to be copied into sched / stream /
wgl3_pallas / parallel.dense) and executed through
``KernelPlan.dispatch`` — the single choke point every production
launch goes through.

Elasticity lives in the key discipline: ``KernelPlan.cache_key()``
includes the mesh identity (axes + shape + device ids,
parallel/mesh.mesh_key), so when the visible device count changes
between runs the plan re-buckets and every kernel-LRU lookup MISSES
instead of serving a compiled launch for a mesh that no longer exists
(tests/test_plan_elastic.py pins this).

The registry (``plan.registry.PLAN_FAMILIES``) is verified against
``contracts.json`` twice: statically by jtflow JTL407 and at runtime
by :func:`verify_registry` (the tier-1 contracts↔plan sync test) — the
plan layer cannot drift from the spec it was seeded from.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Optional

from .registry import PLAN_FAMILIES, family_entry

CONTRACTS_FILE = "contracts.json"


class PlanContractError(RuntimeError):
    """The plan registry and contracts.json disagree — the drift JTL407
    exists to catch, surfaced at runtime with the same wording."""


def repo_root() -> Path:
    """The tree root contracts.json lives in (two levels above plan/)."""
    return Path(__file__).resolve().parents[2]


_CONTRACTS: Optional[dict] = None


def load_contracts(root: Optional[Path] = None) -> Optional[dict]:
    """The checked-in contracts.json (parsed once per process), or None
    when the tree doesn't carry one (an installed package without the
    repo — plans still build, from the registry alone)."""
    global _CONTRACTS
    if root is not None:
        path = Path(root) / CONTRACTS_FILE
        try:
            return json.loads(path.read_text(encoding="utf-8"))
        except (OSError, ValueError):
            return None
    if _CONTRACTS is None:
        path = repo_root() / CONTRACTS_FILE
        try:
            _CONTRACTS = json.loads(path.read_text(encoding="utf-8"))
        except (OSError, ValueError):
            _CONTRACTS = {}
    return _CONTRACTS or None


def verify_registry(contracts: Optional[dict] = None) -> list[str]:
    """The contracts↔plan diff, as a list of mismatch strings (empty =
    in sync). The runtime twin of jtflow JTL407: every spec family must
    resolve to a registry entry with matching module / factory /
    donation set / packed schema, every registry family must appear in
    the spec, declared carries must exist in the spec's carries
    section, and declared mesh axes in its meshes section."""
    if contracts is None:
        contracts = load_contracts()
    if contracts is None:
        return ["contracts.json missing — run `jepsen-tpu lint "
                "--write-contracts`"]
    problems: list[str] = []
    spec = contracts.get("kernels", {})
    carries = set(contracts.get("carries", {}))
    meshes = set(contracts.get("meshes", {}))
    for fam in sorted(set(spec) - set(PLAN_FAMILIES)):
        problems.append(
            f"kernel family {fam!r} is in contracts.json but has no "
            f"KernelPlan registry entry — the plan layer cannot "
            f"dispatch it")
    for fam in sorted(set(PLAN_FAMILIES) - set(spec)):
        problems.append(
            f"plan registry dispatches backend {fam!r}, which "
            f"contracts.json does not declare — dispatch target "
            f"outside the spec")
    for fam in sorted(set(spec) & set(PLAN_FAMILIES)):
        ent, dec = PLAN_FAMILIES[fam], spec[fam]
        for fld in ("module", "factory"):
            if ent[fld] != dec.get(fld):
                problems.append(
                    f"{fam}: registry {fld} {ent[fld]!r} != contracts "
                    f"{dec.get(fld)!r}")
        if sorted(ent["donates"]) != sorted(dec.get("donates", [])):
            problems.append(
                f"{fam}: registry donates {sorted(ent['donates'])} != "
                f"contracts {sorted(dec.get('donates', []))}")
        if (ent["packed"] or None) != dec.get("packed"):
            problems.append(
                f"{fam}: registry packed {ent['packed']!r} != contracts "
                f"{dec.get('packed')!r}")
        if ent["carry"] and ent["carry"] not in carries:
            problems.append(
                f"{fam}: registry carry {ent['carry']!r} is not a "
                f"contracts carries entry ({sorted(carries)})")
        for ax in ent["axes"]:
            if ax not in meshes:
                problems.append(
                    f"{fam}: registry mesh axis {ax!r} is not declared "
                    f"by any mesh construction (contracts meshes: "
                    f"{sorted(meshes)})")
    return problems


def check_registry() -> None:
    """Raise PlanContractError when the registry drifted from the spec
    (dispatch calls this once per process before the first resolve)."""
    problems = verify_registry()
    if problems:
        raise PlanContractError(
            "plan registry out of sync with contracts.json:\n  "
            + "\n  ".join(problems))


@dataclass(frozen=True)
class MeshSpec:
    """The mesh identity a plan keys its compiled launches on."""

    axes: tuple[str, ...]
    shape: tuple[int, ...]
    device_ids: tuple[int, ...]

    @classmethod
    def from_mesh(cls, mesh) -> "MeshSpec":
        from ..parallel.mesh import mesh_key

        axes, shape, ids = mesh_key(mesh)
        return cls(axes=axes, shape=shape, device_ids=ids)

    @property
    def total(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    def key(self) -> tuple:
        return (self.axes, self.shape, self.device_ids)


@dataclass(frozen=True, eq=False)
class KernelPlan:
    """One resolved launch plan: a contracts.json family plus the
    runtime choices (geometry, chunking, batch, mesh, sparsity) the
    planners made for this call shape. `extra` carries family-specific
    builder arguments as a sorted, hashable tuple of (name, value)
    pairs; `model` rides along un-hashed (its cache_key() joins the
    plan key)."""

    family: str
    label: str                      # human-facing kernel string
    model: Any = None
    geometry: Any = None            # DenseConfig / WGLConfig / None
    n_steps: Optional[int] = None
    batch: Optional[int] = None
    chunk: Optional[int] = None
    mesh: Optional[MeshSpec] = None
    sparse: bool = False
    dedup: bool = False
    extra: tuple = ()
    # contract-declared facts, filled by build_plan from the registry:
    donates: tuple = ()
    packed: Optional[str] = None
    carry: Optional[str] = None
    provenance: tuple = ()          # sorted (field, source) pairs

    def cache_key(self) -> tuple:
        """The kernel-LRU key for this plan's compiled launch. Includes
        the mesh identity — the elastic-reshard safety invariant (a
        device-count change can only MISS, never alias)."""
        return ("plan", self.family,
                self.model.cache_key() if self.model is not None else None,
                self.geometry, self.n_steps, self.batch, self.chunk,
                self.mesh.key() if self.mesh is not None else None,
                self.sparse, self.dedup, self.extra)

    @property
    def n_shards(self) -> int:
        """How many contiguous blocks the launch splits its [B] axis
        into: the mesh's device count, 1 without a mesh."""
        return self.mesh.total if self.mesh is not None else 1

    def dispatch(self, *args, **kwargs):
        """Resolve this plan's backend kernel and launch it — THE entry
        every rerouted caller funnels through (plan.dispatch module)."""
        from .dispatch import dispatch

        return dispatch(self, *args, **kwargs)

    def resolve(self):
        from .dispatch import resolve

        return resolve(self)

    def describe(self) -> dict:
        """JSON-friendly dump (the `jepsen-tpu plan --print` payload)."""
        ent = family_entry(self.family)
        return {
            "family": self.family,
            "label": self.label,
            "model": getattr(self.model, "name", None),
            "geometry": repr(self.geometry) if self.geometry is not None
            else None,
            "n_steps": self.n_steps,
            "batch": self.batch,
            "chunk": self.chunk,
            "mesh": {"axes": list(self.mesh.axes),
                     "shape": list(self.mesh.shape)}
            if self.mesh is not None else None,
            "sparse": self.sparse,
            "dedup": self.dedup,
            "extra": {k: repr(v) for k, v in self.extra},
            "backend": {"module": ent["module"], "factory": ent["factory"],
                        "entry": ent.get("entry") or ent["factory"],
                        "role": ent["role"]},
            "donates": list(self.donates),
            "packed": self.packed,
            "carry": self.carry,
            "provenance": dict(self.provenance),
        }


def build_plan(family: str, model: Any = None, geometry: Any = None, *,
               label: Optional[str] = None, n_steps: Optional[int] = None,
               batch: Optional[int] = None, chunk: Optional[int] = None,
               mesh: Any = None, sparse: bool = False, dedup: bool = False,
               provenance: Optional[dict] = None,
               **extra) -> KernelPlan:
    """A KernelPlan for `family`, contract fields filled from the
    registry (which JTL407 + verify_registry pin to contracts.json).
    `mesh` accepts a jax Mesh or a MeshSpec."""
    ent = family_entry(family)
    if mesh is not None and not isinstance(mesh, MeshSpec):
        mesh = MeshSpec.from_mesh(mesh)
    return KernelPlan(
        family=family, label=label or family, model=model,
        geometry=geometry, n_steps=n_steps, batch=batch, chunk=chunk,
        mesh=mesh, sparse=sparse, dedup=dedup,
        extra=tuple(sorted(extra.items())),
        donates=tuple(ent["donates"]), packed=ent["packed"],
        carry=ent["carry"],
        provenance=tuple(sorted((provenance or {}).items())))


def plan_report(family: Optional[str] = None) -> dict:
    """The `jepsen-tpu plan --print` document: per-family resolved plan
    skeletons (contract facts + backend + current-platform mesh hints)
    plus the registry↔contracts sync verdict — the plan layer's
    tools/print_profile.py equivalent."""
    from ..ops.limits import limits

    fams = [family] if family else sorted(PLAN_FAMILIES)
    for f in fams:
        family_entry(f)             # unknown family fails loudly
    lim = limits()
    try:
        import jax

        devices = jax.device_count()
        processes = jax.process_count()
    except Exception:
        devices = processes = None
    report = {
        "contracts": str(repo_root() / CONTRACTS_FILE),
        "sync": verify_registry() or "ok",
        "devices": devices,
        "processes": processes,
        "limits": {"sparse_mode": lim.sparse_mode,
                   "dedup_mode": lim.dedup_mode,
                   "long_scan_chunk": lim.long_scan_chunk,
                   "step_bucket_floor": lim.step_bucket_floor,
                   "batch_bucket_floor": lim.batch_bucket_floor},
        "families": {},
    }
    for f in fams:
        ent = family_entry(f)
        report["families"][f] = {
            "module": ent["module"], "factory": ent["factory"],
            "entry": ent.get("entry") or ent["factory"],
            "role": ent["role"], "donates": list(ent["donates"]),
            "packed": ent["packed"], "carry": ent["carry"],
            "axes": list(ent["axes"]),
        }
    return report
