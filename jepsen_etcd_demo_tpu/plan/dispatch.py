"""Plan dispatch — the ONE routing point under every kernel launch.

Before this module, the backend choice was copied wherever a launch
happened: ``wgl3_pallas.packed_batch_checker`` picked pallas-vs-XLA for
single-device batches, ``parallel.dense.sharded_packed_batch_checker``
re-made the same choice per shard, ``sched._dense_bucket_launcher``
re-made the sharded-vs-local choice per bucket, and
``check_encoded_general`` / ``run_long_dense`` each carried their own
lattice-vs-pallas-vs-XLA ladder for long sweeps. Those four copies now
live here once, as PLANNERS that return a :class:`KernelPlan`:

  plan_dense_batch    one batched dense launch (single- or multi-
                      device, pallas or XLA, grouped or not)
  plan_long_sweep     the host-chunked long-sweep family (lattice /
                      pallas-resumable / sparse / dedup / plain chunk)
  plan_stream_chunk   the streaming engine's resumable chunk kernel
  plan_resumable      the wgl2 sort-ladder chunk kernel
  plan_elle_batch     the vmapped corpus-of-graphs closure

and EXECUTORS — ``resolve(plan)`` (the compiled launch, through the
sched kernel LRU keyed by ``plan.cache_key()``, which carries the mesh
identity: an elastic re-shard can only miss) and ``dispatch(plan,
...)`` / ``dispatch_long(...)`` (launch it). The first resolve in a
process verifies the registry against contracts.json
(``core.check_registry``) so a drifted plan layer fails loudly before
it launches anything.
"""

from __future__ import annotations

from typing import Any, Optional

from .core import (KernelPlan, MeshSpec, build_plan, check_registry,
                   load_contracts)
from .registry import PLAN_FAMILIES, backend_callable

_CHECKED = False


def _ensure_checked() -> None:
    global _CHECKED
    if not _CHECKED:
        # Trees without a contracts.json (installed package) skip the
        # gate; in-repo, drift fails the first dispatch loudly.
        if load_contracts() is not None:
            check_registry()
        _CHECKED = True


def resolve(plan: KernelPlan):
    """The compiled launch callable for a plan, through the sched
    kernel LRU (hit/miss accounted; bounded by
    limits().kernel_cache_entries). The key is plan.cache_key() — mesh
    identity included, so a re-shard (device count changed between
    runs) misses into a fresh build instead of aliasing a compiled
    launch for a mesh that no longer exists."""
    from ..sched.compile_cache import kernel_cache

    _ensure_checked()
    builder = _BUILDERS.get(plan.family)
    if builder is None:
        raise KeyError(
            f"no dispatch builder for kernel family {plan.family!r}")
    return kernel_cache().get(plan.cache_key(), lambda: builder(plan))


def dispatch(plan: KernelPlan, *args, **kwargs):
    """Resolve + launch: the single choke point (KernelPlan.dispatch).
    Launches carry the plan's identity into the scaling ledger
    (obs/ledger.py) — callers that know the padding economics open a
    richer launch_context themselves; the merge keeps their fields."""
    from ..obs import ledger as obs_ledger

    fn = resolve(plan)
    with obs_ledger.launch_context(**obs_ledger.plan_context(plan)):
        return fn(*args, **kwargs)


def _extra(plan: KernelPlan) -> dict:
    return dict(plan.extra)


def _mesh_of(plan: KernelPlan):
    """Rebuild the jax Mesh a plan's MeshSpec describes (the spec is
    the hashable identity; the Mesh itself is rebuilt from the CURRENT
    device set — if the devices moved the ids won't match and the key
    already missed)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    spec = plan.mesh
    by_id = {d.id: d for d in jax.devices()}
    try:
        devs = [by_id[i] for i in spec.device_ids]
    except KeyError as e:
        raise RuntimeError(
            f"plan {plan.family} names device id {e.args[0]} which is "
            f"not visible — re-plan on the current platform (elastic "
            f"re-shard)") from None
    arr = np.array(devs).reshape(spec.shape)
    return Mesh(arr, spec.axes)


# -- per-family builders ---------------------------------------------------
# Each returns the launch callable for one plan. Thin by design: the
# factories (and their obs.instrument_kernel wrapping) stay in the
# backend modules contracts.json points at; this table only maps a
# family to its factory's argument convention.

def _b_wgl2_single(p):
    return backend_callable("wgl2-single")(p.model, p.geometry)


def _b_wgl2_batch(p):
    return backend_callable("wgl2-batch")(p.model, p.geometry)


def _b_wgl2_chunk(p):
    return backend_callable("wgl2-chunk")(p.model, p.geometry,
                                          **_extra(p))


def _b_wgl2_sort_sharded(p):
    return backend_callable("wgl2-sort-sharded")(p.model, p.geometry,
                                                 _mesh_of(p))


def _b_wgl3_single(p):
    return backend_callable("wgl3-single")(p.model, p.geometry)


def _b_wgl3_batch(p):
    return backend_callable("wgl3-batch")(p.model, p.geometry)


def _b_wgl3_chunk(p):
    return backend_callable("wgl3-chunk")(p.model, p.geometry, p.chunk)


def _b_wgl3_chunk_dedup(p):
    return backend_callable("wgl3-chunk-dedup")(
        p.model, p.geometry, p.chunk, _extra(p)["min_frontier"])


def _b_wgl3_sparse_chunk(p):
    e = _extra(p)
    return backend_callable("wgl3-sparse-chunk")(
        p.model, p.geometry, e["sparse_plan"], p.chunk,
        memo_slots=e.get("memo_slots", 0))


def _b_wgl3_sparse_chunk_dedup(p):
    e = _extra(p)
    return backend_callable("wgl3-sparse-chunk-dedup")(
        p.model, p.geometry, e["sparse_plan"], p.chunk,
        e["min_frontier"], e.get("memo_slots", 0))


def _b_wgl3_dense_sharded(p):
    return backend_callable("wgl3-dense-sharded")(p.model, p.geometry,
                                                  _mesh_of(p))


def _b_wgl3_pallas(p):
    return backend_callable("wgl3-pallas")(p.model, p.geometry)


def _b_wgl3_pallas_grouped(p):
    return backend_callable("wgl3-pallas-grouped")(
        p.model, p.geometry, _extra(p)["group"])


def _b_wgl3_pallas_prep(p):
    return backend_callable("wgl3-pallas-prep")(p.model, p.geometry)


def _b_wgl3_pallas_resumable(p):
    return backend_callable("wgl3-pallas-resumable")(p.model, p.geometry,
                                                     **_extra(p))


def _b_wgl3_pallas_sparse_resumable(p):
    return backend_callable("wgl3-pallas-sparse-resumable")(
        p.model, p.geometry, **_extra(p))


def _b_wgl3_pallas_sharded(p):
    e = _extra(p)
    return backend_callable("wgl3-pallas-sharded")(
        p.model, p.geometry, _mesh_of(p), group=e.get("group", 1))


def _b_wgl3_lattice_chunk(p):
    e = _extra(p)
    return backend_callable("wgl3-lattice-chunk")(
        p.model, p.geometry, _mesh_of(p), axis=e.get("axis", "lattice"),
        plan=e.get("sparse_plan"), canon=p.dedup,
        min_frontier=e.get("min_frontier", 0),
        memo_slots=e.get("memo_slots", 0))


def _b_lattice_transitions(p):
    return backend_callable("lattice-transitions")(p.model, p.geometry)


def _b_wgl3_multislice(p):
    return backend_callable("wgl3-dense-multislice")(p.model, p.geometry,
                                                     _mesh_of(p))


def _b_wgl3_encode(p):
    e = _extra(p)
    return backend_callable("wgl3-encode")(
        e["k_slots"], e["e_cap"], p.n_steps)


def _b_wgl3_encode_sharded(p):
    e = _extra(p)
    return backend_callable("wgl3-encode-sharded")(
        e["k_slots"], e["e_cap"], p.n_steps, _mesh_of(p))


def _b_elle_closure(p):
    return backend_callable("elle-closure")(_extra(p)["n_pad"])


def _b_elle_closure_batch(p):
    e = _extra(p)
    return backend_callable("elle-closure-batch")(e["n_pad"], p.batch)


def _b_elle_tiled(p):
    e = _extra(p)
    return backend_callable("elle-closure-tiled")(e["nb"], e["tile"])


def _b_elle_tiled_pallas(p):
    e = _extra(p)
    return backend_callable("elle-closure-tiled-pallas")(
        e["nb"], e["tile"], e["cap"], e["use_pallas"],
        interpret=e.get("interpret", False))


_BUILDERS = {
    "elle-closure": _b_elle_closure,
    "elle-closure-batch": _b_elle_closure_batch,
    "elle-closure-tiled": _b_elle_tiled,
    "elle-closure-tiled-pallas": _b_elle_tiled_pallas,
    "lattice-transitions": _b_lattice_transitions,
    "wgl2-batch": _b_wgl2_batch,
    "wgl2-chunk": _b_wgl2_chunk,
    "wgl2-single": _b_wgl2_single,
    "wgl2-sort-sharded": _b_wgl2_sort_sharded,
    "wgl3-batch": _b_wgl3_batch,
    "wgl3-chunk": _b_wgl3_chunk,
    "wgl3-chunk-dedup": _b_wgl3_chunk_dedup,
    "wgl3-dense-multislice": _b_wgl3_multislice,
    "wgl3-dense-sharded": _b_wgl3_dense_sharded,
    "wgl3-encode": _b_wgl3_encode,
    "wgl3-encode-sharded": _b_wgl3_encode_sharded,
    "wgl3-lattice-chunk": _b_wgl3_lattice_chunk,
    "wgl3-pallas": _b_wgl3_pallas,
    "wgl3-pallas-grouped": _b_wgl3_pallas_grouped,
    "wgl3-pallas-prep": _b_wgl3_pallas_prep,
    "wgl3-pallas-resumable": _b_wgl3_pallas_resumable,
    "wgl3-pallas-sharded": _b_wgl3_pallas_sharded,
    "wgl3-pallas-sharded-prep": _b_wgl3_pallas_sharded,
    "wgl3-pallas-sparse-resumable": _b_wgl3_pallas_sparse_resumable,
    "wgl3-single": _b_wgl3_single,
    "wgl3-sparse-chunk": _b_wgl3_sparse_chunk,
    "wgl3-sparse-chunk-dedup": _b_wgl3_sparse_chunk_dedup,
}

assert set(_BUILDERS) == set(PLAN_FAMILIES), (
    sorted(set(_BUILDERS) ^ set(PLAN_FAMILIES)))


# -- planners: the routing policy, in ONE copy -----------------------------

def plan_dense_batch(model, cfg, n_steps: Optional[int] = None,
                     batch: Optional[int] = None,
                     mesh: Any = None, shard: bool = True) -> KernelPlan:
    """THE dense batched-launch route (was three copies:
    wgl3_pallas.packed_batch_checker, dense.sharded_packed_batch_checker
    and sched._dense_bucket_launcher): single- vs multi-device by the
    CURRENT platform (or the caller's mesh), pallas vs XLA by the
    per-device shard's envelope, grouped pallas when the shard splits
    into whole groups. The resolved callable takes the stacked
    (slot_tabs, slot_active, targets) arrays and returns DEVICE packed
    i32 rows — i32[B, 6] (wgl3.PACKED_FIELDS_XLA) on the XLA routes,
    i32[B, 5] (wgl3.PACKED_FIELDS) on pallas; wgl3.unpack_np accepts
    both widths.

    Grouped-kernel rationale (measured on v5e, round 4): G histories
    per pallas program amortize per-step instruction overhead — ~48 ms
    device time for the 1024x150-op bench corpus at G=16 vs ~230 ms
    per-history — bit-identical to the per-history kernel. ONLY for
    Sp=8 models: wider states spill Mosaic's scoped VMEM at full group
    size, and the reduced group that fits (G=4 at Sp=32) measured 14%
    SLOWER than per-history. Small batches stay per-history (grouping
    would pad them with dead work). A batch whose targets overflow SMEM
    runs as several launches inside the kernel's jit
    (wgl3_pallas.launch_parts), never on a different kernel."""
    import jax

    from ..ops import wgl3_pallas
    from ..ops.limits import limits

    long_max = limits().long_scan_max
    if n_steps is not None and n_steps > long_max:
        raise ValueError(
            f"n_steps={n_steps} exceeds one scan program "
            f"(long_scan_max={long_max}); use "
            f"check_batch_encoded_auto or wgl3.check_steps3_long")
    mesh_src = "caller" if mesh is not None else "platform"
    if shard and mesh is None and jax.device_count() > 1 \
            and (batch or 0) > 1:
        from ..parallel.dense import batch_mesh

        mesh = batch_mesh()
    prov = {"mesh": mesh_src, "backend": "envelope"}
    if mesh is not None:
        spec = mesh if isinstance(mesh, MeshSpec) else \
            MeshSpec.from_mesh(mesh)
        d = spec.total
        local_batch = None if batch is None else (batch + d - 1) // d
        if wgl3_pallas.use_pallas(cfg, n_steps):
            G = wgl3_pallas.pallas_group_size(cfg, n_steps, local_batch)
            if G > 1 and local_batch % G == 0:
                return build_plan(
                    "wgl3-pallas-sharded", model, cfg,
                    label="wgl3-dense-pallas-grouped-sharded",
                    n_steps=n_steps, batch=batch, mesh=spec, group=G,
                    provenance=prov)
            return build_plan(
                "wgl3-pallas-sharded", model, cfg,
                label="wgl3-dense-pallas-sharded", n_steps=n_steps,
                batch=batch, mesh=spec, provenance=prov)
        return build_plan(
            "wgl3-dense-sharded", model, cfg, label="wgl3-dense-sharded",
            n_steps=n_steps, batch=batch, mesh=spec, provenance=prov)
    if wgl3_pallas.use_pallas(cfg, n_steps):
        G = wgl3_pallas.pallas_group_size(cfg, n_steps, batch)
        if G > 1:
            return build_plan("wgl3-pallas-grouped", model, cfg,
                              label="wgl3-dense-pallas-grouped",
                              n_steps=n_steps, batch=batch, group=G,
                              provenance=prov)
        return build_plan("wgl3-pallas", model, cfg,
                          label="wgl3-dense-pallas", n_steps=n_steps,
                          batch=batch, provenance=prov)
    return build_plan("wgl3-batch", model, cfg, label="wgl3-dense",
                      n_steps=n_steps, batch=batch, provenance=prov)


def launch_multiple(model, cfg, n_steps: Optional[int] = None,
                    batch: Optional[int] = None, mesh: Any = None) -> int:
    """The [B]-axis padding multiple a plan_dense_batch launch of this
    shape needs (sched pads buckets to it BEFORE planning — the bucket
    can inflate a 1-history part onto the sharded route)."""
    import jax

    if mesh is None:
        if jax.device_count() <= 1 or (batch or 0) <= 1:
            return 1
        from ..parallel.dense import batch_mesh

        mesh = batch_mesh()
    from ..parallel.dense import batch_multiple

    return batch_multiple(model, cfg, mesh, n_steps=n_steps, batch=batch)


def plan_long_sweep(model, cfg, lattice_mesh: Any = None,
                    chunk: Optional[int] = None) -> KernelPlan:
    """The host-chunked long-sweep family for this geometry on this
    platform: the lattice-sharded chunk kernel when a mesh is given
    (the caller derived a lattice-feasible cfg), else the fused pallas
    resumable windows when the envelope allows, else the XLA chunk fn —
    with the sparse active-tile engine and the frontier-dedup pass
    reflected in the family exactly as the sweep will engage them. The
    plan is DESCRIPTIVE for the host loop (dispatch_long drives the
    loop); its key is what the loop's chunk kernels resolve under."""
    from ..ops import wgl3, wgl3_pallas
    from ..ops.wgl3_sparse import memo_slots_for, sparse_plan

    prov = {"backend": "envelope"}
    if lattice_mesh is not None:
        from ..parallel.lattice import lattice_sparse_plan
        from ..parallel.mesh import mesh_total

        d = mesh_total(lattice_mesh)
        sp = lattice_sparse_plan(cfg, d)
        return build_plan(
            "wgl3-lattice-chunk", model, cfg,
            label=("wgl3-dense-lattice-sparse" if sp is not None
                   else "wgl3-dense-lattice-sharded"),
            chunk=chunk, mesh=lattice_mesh, sparse=sp is not None,
            sparse_plan=sp, provenance=prov | {"mesh": "lattice"})
    if wgl3_pallas.use_pallas(cfg):
        if wgl3_pallas.pallas_sparse_selected(cfg):
            return build_plan("wgl3-pallas-sparse-resumable", model, cfg,
                              label="wgl3-dense-pallas-sparse-chunked",
                              chunk=chunk, sparse=True, provenance=prov)
        return build_plan("wgl3-pallas-resumable", model, cfg,
                          label="wgl3-dense-pallas-chunked", chunk=chunk,
                          provenance=prov)
    sp = sparse_plan(cfg)
    if sp is not None:
        return build_plan("wgl3-sparse-chunk", model, cfg,
                          label="wgl3-dense-sparse-chunked", chunk=chunk,
                          sparse=True, sparse_plan=sp,
                          memo_slots=memo_slots_for(sp), provenance=prov)
    if _table_dedup_possible():
        # Family only — whether a given HISTORY carries symmetry (and
        # thus takes the dedup twin) is per-call; the host loop decides
        # per history exactly as before.
        return build_plan("wgl3-chunk-dedup", model, cfg,
                          label="wgl3-dense-chunked", chunk=chunk,
                          dedup=True,
                          min_frontier=wgl3.dedup_min_frontier_active(),
                          provenance=prov)
    return build_plan("wgl3-chunk", model, cfg, label="wgl3-dense-chunked",
                      chunk=chunk, provenance=prov)


def _table_dedup_possible() -> bool:
    from ..ops.limits import limits

    return limits().dedup_mode == 2


def dispatch_long(rs, model, cfg, lattice_mesh: Any = None,
                  chunk: Optional[int] = None,
                  time_budget_s: Optional[float] = None) -> dict:
    """Run one long (host-chunked) dense sweep under the planned
    family. This is the one copy of the lattice / pallas / XLA ladder
    that run_long_dense and check_encoded_general each used to carry;
    result schema is the chunked sweep's, with the plan's family
    stamped as `plan_family`."""
    plan = plan_long_sweep(model, cfg, lattice_mesh=lattice_mesh,
                           chunk=chunk)
    if plan.family == "wgl3-lattice-chunk":
        from ..parallel.lattice import check_steps_lattice_long

        out = check_steps_lattice_long(rs, model, cfg, mesh=lattice_mesh,
                                       chunk=chunk,
                                       time_budget_s=time_budget_s)
    elif plan.family in ("wgl3-pallas-resumable",
                         "wgl3-pallas-sparse-resumable"):
        from ..ops.wgl3_pallas import check_steps3_long_pallas

        out = check_steps3_long_pallas(rs, model, cfg,
                                       time_budget_s=time_budget_s)
    else:
        from ..ops.wgl3 import check_steps3_long

        out = check_steps3_long(rs, model, cfg, chunk=chunk,
                                time_budget_s=time_budget_s)
    out.setdefault("kernel", plan.label)
    out["plan_family"] = plan.family
    return out


def plan_stream_chunk(model, cfg, chunk: int) -> KernelPlan:
    """The streaming engine's resumable chunk kernel: ALWAYS the plain
    (no-canonicalization) wgl3 chunk fn — a live stream cannot know
    which pending ops never return (ops/canon.py), and post-hoc sweeps
    of short histories are canon-free too, so streamed and post-hoc
    metrics stay bit-identical."""
    return build_plan("wgl3-chunk", model, cfg,
                      label="wgl3-dense-stream-chunked", chunk=chunk,
                      provenance={"backend": "stream"})


def plan_resumable(model, cfg, canon: bool = False) -> KernelPlan:
    """The wgl2 sort-ladder resumable chunk kernel; `canon` selects the
    frontier-canonicalizing twin (ops/canon.py — the sort ladder is
    where dedup pays, so AUTO mode engages it per history)."""
    extra = {"canon": True} if canon else {}
    return build_plan("wgl2-chunk", model, cfg, label="wgl2-sort-resumable",
                      dedup=canon, provenance={"backend": "sort-ladder"},
                      **extra)


def plan_elle_batch(n_pad: int, batch: int) -> KernelPlan:
    """One bucketed corpus-of-graphs closure launch (ops/cycles.py)."""
    return build_plan("elle-closure-batch", batch=batch, n_pad=n_pad,
                      label="elle-closure-batch",
                      provenance={"backend": "elle"})


def plan_elle_single(n_pad: int) -> KernelPlan:
    """One single-graph dense closure launch (ops/cycles.py)."""
    return build_plan("elle-closure", n_pad=n_pad, label="elle-closure",
                      provenance={"backend": "elle"})


def plan_device_encode(k_slots: int, e_cap: int, r_cap: int,
                       batch: Optional[int] = None,
                       mesh: Any = None) -> KernelPlan:
    """One device-side history-encode launch (ops/encode_device.py):
    events[(B,) e_cap, 6] -> the return-major slot-table arrays the
    dense checkers consume, built on-device. Sharded over the batch
    mesh when the caller passes one (parallel/dense.py — each shard
    expands its own histories; only the compact event stream crosses
    the H2D boundary), single-device otherwise."""
    prov = {"backend": "device-encode"}
    if mesh is not None:
        spec = mesh if isinstance(mesh, MeshSpec) else \
            MeshSpec.from_mesh(mesh)
        return build_plan("wgl3-encode-sharded",
                          label="wgl3-encode-sharded", n_steps=r_cap,
                          batch=batch, mesh=spec, k_slots=k_slots,
                          e_cap=e_cap, provenance=prov | {"mesh": "caller"})
    return build_plan("wgl3-encode", label="wgl3-encode", n_steps=r_cap,
                      batch=batch, k_slots=k_slots, e_cap=e_cap,
                      provenance=prov)


class LaunchPipeline:
    """Depth-bounded in-flight launch window for bucketed corpus
    dispatch — the ``wgl3.check_steps3_long`` double-buffering
    discipline lifted to WHOLE launches. The caller stages + dispatches
    launch N+1 (async: host prep and the H2D enqueue overlap launch N's
    device execute) and push()es an entry per launch; once
    ``limits().pod_pipeline_depth`` launches are in flight, submit()
    resolves (fetches) the OLDEST entry before admitting the new one,
    so undrained device results stay bounded and fetch round trips hide
    under real device work instead of stalling the tail.

    depth=1 restores the fetch-after-every-launch synchronous loop; a
    depth at or beyond the launch count reproduces the old unbounded
    dispatch-all-then-drain behaviour. Ordering and results are
    bit-identical at any depth — the window only reorders WHEN fetches
    happen, never what was launched.

    ``rollback()`` is the mid-pipeline falsification escape hatch: it
    discards every speculative in-flight entry WITHOUT resolving it
    (speculated launches were wasted device work, not wrong answers)
    and marks the pipeline aborted so a fail-fast caller stops
    submitting (tests/test_pod_scaling.py pins depth bounding and
    rollback)."""

    def __init__(self, depth: Optional[int] = None, resolve=None):
        from ..ops.limits import limits
        from ..sched.pipeline import InflightWindow

        if depth is None:
            depth = limits().pod_pipeline_depth
        self._win = InflightWindow(depth)
        self._resolve = resolve
        self._aborted = False
        self.dispatched = 0
        self.rolled_back = 0

    @property
    def depth(self) -> int:
        return self._win.depth

    @property
    def aborted(self) -> bool:
        return self._aborted

    def __len__(self) -> int:
        return len(self._win)

    def _resolve_one(self):
        entry = self._win.pop()
        return self._resolve(entry) if self._resolve is not None else entry

    def submit(self, entry) -> list:
        """Admit one dispatched launch; returns the resolved entries the
        window had to retire to make room (possibly none)."""
        if self._aborted:
            raise RuntimeError("submit after rollback")
        drained = []
        while self._win.full():
            drained.append(self._resolve_one())
        self._win.push(entry)
        self.dispatched += 1
        return drained

    def drain(self) -> list:
        """Resolve every remaining in-flight entry, oldest first."""
        out = []
        while self._win:
            out.append(self._resolve_one())
        return out

    def rollback(self) -> int:
        """Discard the speculative window (mid-pipeline falsification):
        in-flight entries are dropped unresolved, the pipeline refuses
        further submits. Returns the number of launches discarded."""
        n = len(self._win)
        self._win.clear()
        self._aborted = True
        self.rolled_back += n
        return n
