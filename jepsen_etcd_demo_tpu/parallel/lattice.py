"""Lattice-sharded dense WGL search: ONE wide history, many devices.

The dense subset-lattice kernel (ops/wgl3.py) holds the search frontier as
the characteristic table u32[S, W] over (state, pending-mask) configs,
W = 2^(K-5) packed words. Past K ~ 17 the table outgrows one device's cell
budget and the single-device ladder falls back to the sort kernel or the
host-chunked sweep (ops/wgl3_pallas.check_encoded_general). This module
shards the table's WORD axis over a mesh axis instead — the build's
sequence-parallelism analogue (SURVEY.md §5.7): history length is the
sequence, the lattice is the per-step state, and each device owns the
2^(K-5)/D words whose global index falls in its contiguous shard.

What each table operation becomes under the shard (device count D = 2^dbits,
local words W_loc = W/D, lbits = log2(W_loc); global word index = low lbits
local | high dbits device):

  * expanding slot j < 5            in-word shift — LOCAL
  * expanding 5 <= j < 5+lbits      local word-axis reshape — LOCAL
  * expanding j >= 5+lbits          the mask bit lives in the DEVICE index:
                                    devices with bit b = j-5-lbits clear OR
                                    their fired configs into partner
                                    d | 1<<b — ONE lax.ppermute over ICI
  * pruning at return t             same split; the remote case is the
                                    reverse ppermute (bit-set partner sends
                                    its half down), selected by lax.switch
                                    over the dbits static permutations
  * frontier size / death           psum of local popcount / any

Exactness is unchanged — the sharded table is the same whole config space,
just partitioned; no capacity, no overflow, no dropped configs. Verdicts
are bit-identical to the single-device dense kernel (differentially
tested), and the chunked host loop (`check_steps_lattice_long`) mirrors
check_steps3_long with the carry staying sharded on-device between chunks.

Production routing: check_encoded_general's dense-chunked rung upgrades to
this path automatically when jax.device_count() > 1 and the geometry
shards (W >= D) — with the cell budget scaled by D, geometries the
single-device rung must refuse become checkable at all.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..models.base import Model
from ..obs import instrument_kernel, record_check_result
from ..ops import wgl3
from ..ops.encode import ReturnSteps
from ..ops.limits import limits
from ..ops.wgl3 import DenseConfig, _LO_MASK
from .mesh import (host_count, make_mesh, mesh_key as _mesh_key,
                   mesh_total, pod_mesh, requested_shape,
                   resolve_axis as _resolve_axis)

_CACHE: dict[tuple, Any] = {}


def lattice_mesh(n_devices: int | None = None) -> Mesh:
    """The table-word-axis mesh. Single host: the 1-axis ("lattice",)
    mesh (or an explicit N-D shape via --mesh-shape, axes
    ("host", "lattice")). Multi-host: the process-major
    ("host", "lattice") pod mesh — the sweep's collectives name the
    axis TUPLE, so the word axis shards (and psum/pmax/ppermute
    all-reduce) across DCN and ICI jointly."""
    if n_devices is None:
        shape = requested_shape()
        if shape is not None:
            if len(shape) > 2:
                raise ValueError(
                    f"--mesh-shape {'x'.join(map(str, shape))}: the "
                    f"lattice lane builds at most 2-D "
                    f"('host', 'lattice') meshes")
            if len(shape) > 1:
                return make_mesh(axes=("host", "lattice"), shape=shape)
            return make_mesh(shape[0], axes=("lattice",))
        if host_count() > 1:
            return pod_mesh(axes=("host", "lattice"))
    return make_mesh(n_devices, axes=("lattice",))


def lattice_dense_config(model: Model, k_slots: int, max_value: int,
                         n_devices: int,
                         budget: int | None = None) -> DenseConfig | None:
    """DenseConfig for the SHARDED lattice: the cell budget scales with the
    device count (each device holds cells/D), and the word axis must split
    evenly — D a power of two with W >= D (the ppermute pairing addresses
    devices by mask bits). Infeasible platforms get None so routing falls
    back to the single-device rung instead of crashing mid-check."""
    if n_devices < 2 or n_devices & (n_devices - 1):
        return None
    if budget is None:
        budget = limits().dense_cell_budget_chunked * n_devices
    cfg = wgl3.dense_config(model, k_slots, max_value, budget=budget)
    if cfg is None or (1 << (cfg.k_slots - 5)) < n_devices:
        return None
    return cfg


def _build_local_step(model: Model, cfg: DenseConfig, axis: str, d: int,
                      plan=None, canon: bool = False,
                      min_frontier: int = 0, memo_slots: int = 0):
    """The per-device scan body over one shard of the table. Mirrors
    wgl3.make_step_fn3 exactly (same banking/closure/prune semantics, same
    metrics) with the word axis split over `axis`.

    ``canon`` enables the per-step frontier canonicalization pass
    (ops/canon.py) SHARD-LOCALLY: the caller filters the exchange
    network to pairs whose slot bits stay inside the shard
    (max_bit = 5 + log2(w_loc)), which is sound because every
    compare-exchange is individually sound — device-bit pairs are
    simply not reduced. The engage decision keys on the psum'd global
    frontier size, so every device takes the same branch. ``memo_slots``
    enables the sparse engine's per-tile seen memo per shard (consumed
    popcounts, ops/wgl3_sparse.make_step_fn3_sparse rationale); the
    nothing-eligible skip keys on the psum'd eligible count so the
    branch — and the ppermutes inside the sweep — stay collective-
    consistent.

    With a `plan` (ops/wgl3_sparse.SparsePlan built on the SHARD width),
    each closure round runs the sparse active-tile sweep over the shard's
    LIVE tiles: occupancy is shard-local, but the dense/sparse decision
    comes from the ALL-REDUCED density signal (psum of live tiles + pmax
    of the per-shard work-list pressure), so every device takes the same
    branch and the branch-internal ppermutes stay collective-consistent.
    A shard whose live tiles overflow the work list forces a dense round
    EVERYWHERE — configs are never dropped. Verdicts stay bit-identical
    to the single-device kernel (same monotone fixpoint argument as
    ops/wgl3_sparse.py, with the device-bit fires crossing the mesh in
    both formulations)."""
    K, S = cfg.k_slots, cfg.n_states
    assert K >= 5 and S <= 32
    W = 1 << (K - 5)
    assert W % d == 0 and (d & (d - 1)) == 0
    w_loc = W // d
    lbits = w_loc.bit_length() - 1
    dbits = d.bit_length() - 1
    lo_masks = jnp.asarray(np.array(_LO_MASK, dtype=np.uint32))
    full = jnp.uint32(0xFFFFFFFF)
    w_idx_loc = jnp.arange(w_loc, dtype=jnp.int32)

    def dev():
        return jax.lax.axis_index(axis)

    def allowed_mask(t):
        """u32[w_loc]: this shard's positions whose mask has bit t CLEAR
        (global word index = dev * w_loc + local)."""
        in_word = lo_masks[jnp.minimum(t, 4)]
        w_glob = dev() * w_loc + w_idx_loc
        word_level = jnp.where(
            ((w_glob >> jnp.maximum(t - 5, 0)) & 1) == 0, full,
            jnp.uint32(0))
        return jnp.where(t < 5, jnp.broadcast_to(in_word, (w_loc,)),
                         word_level)

    def or_reduce(tj, src):
        acc = jnp.zeros_like(src)
        for s in range(S):
            sel = tj[s].reshape((S,) + (1,) * (src.ndim - 1))
            acc = acc | jnp.where(sel, src[s][None], jnp.uint32(0))
        return acc

    def expand(T, trans, allowed):
        """One Gauss-Seidel sweep over all K slots; high slots cross the
        mesh with one ppermute each."""
        for j in range(K):
            src = T & allowed[None, :]
            if j < 5:
                fired = or_reduce(trans[j], src & _LO_MASK[j])
                T = T | (fired << np.uint32(1 << j))
            elif j - 5 < lbits:
                lo_w, hi = 1 << (j - 5), w_loc >> (j - 4)
                Tr = T.reshape(S, hi, 2, lo_w)
                srcj = src.reshape(S, hi, 2, lo_w)[:, :, 0, :]
                fired = or_reduce(trans[j], srcj)
                T = jnp.stack([Tr[:, :, 0, :], Tr[:, :, 1, :] | fired],
                              axis=2).reshape(S, w_loc)
            else:
                b = j - 5 - lbits
                src_dev = ((dev() >> b) & 1) == 0
                fired = or_reduce(trans[j], src)
                fired = jnp.where(src_dev, fired, jnp.uint32(0))
                recv = jax.lax.ppermute(
                    fired, axis,
                    perm=[(p, p | (1 << b)) for p in range(d)
                          if not (p >> b) & 1])
                T = T | recv
        return T

    def prune_local(T, t, allowed):
        """t's mask bit is in-word or in the LOCAL word bits: the
        single-device addressing verbatim (w_loc in place of W)."""
        shift = jnp.where(t < 5, jnp.uint32(1) << jnp.minimum(
            t.astype(jnp.uint32), jnp.uint32(4)), jnp.uint32(0))
        wsel = jnp.where(t < 5, w_idx_loc,
                         w_idx_loc | (jnp.int32(1)
                                      << jnp.maximum(t - 5, 0)))
        # Clamp: when t's bit is beyond the local bits this branch is not
        # taken (lax.switch routes to a remote branch); clamp keeps the
        # gather in bounds for the untaken trace.
        wsel = jnp.minimum(wsel, w_loc - 1)
        return (T[:, wsel] >> shift) & allowed[None, :]

    def prune_remote(b):
        def f(T, t, allowed):
            recv = jax.lax.ppermute(
                T, axis,
                perm=[(p, p ^ (1 << b)) for p in range(d) if (p >> b) & 1])
            return recv & allowed[None, :]
        return f

    def prune(T, t, allowed):
        # switch index: 0 = local bit, 1+b = device bit b.
        idx = jnp.clip(t - 5 - lbits + 1, 0, dbits)
        return jax.lax.switch(
            idx,
            [lambda T, t, a: prune_local(T, t, a)]
            + [prune_remote(b) for b in range(dbits)],
            T, t, allowed)

    # -- shard-local occupancy tiling (telemetry always; sparse when a
    #    plan is given) — the one shared tiling policy, clamped to the
    #    SHARD width (wgl3.live_tile_geometry).
    lim = limits()
    if plan is not None:
        tile, nt_loc = plan.tile_words, plan.n_tiles
    else:
        tile, nt_loc = wgl3.live_tile_geometry(cfg, words=w_loc)
    nt_glob = nt_loc * d
    tbits = tile.bit_length() - 1
    tile_off = jnp.arange(tile, dtype=jnp.int32)
    memo = memo_slots > 0
    assert not memo or (plan is not None and memo_slots == nt_loc), \
        (memo_slots, nt_loc)
    if plan is not None:
        CAP = plan.cap
        cap_ids = jnp.arange(CAP, dtype=jnp.int32)
        thresh_glob = (nt_glob if lim.sparse_mode == 2 else
                       max(1, nt_glob * lim.sparse_density_threshold_pct
                           // 100))
    if canon:
        from ..ops.canon import apply_step_canon, make_table_canon

        canon_fn = make_table_canon(w_loc)

    def occupancy(T):
        any_w = jnp.any(T != jnp.uint32(0), axis=0)
        occ_t = jnp.any(any_w.reshape(nt_loc, tile), axis=1)
        return occ_t, jnp.sum(occ_t, dtype=jnp.int32)

    def tile_popcounts(T):
        """Shard-local per-tile config counts; the memo loop carries
        the vector between rounds so eligibility and the psum'd
        convergence check share one reduce (the wgl3_sparse twin's
        rationale)."""
        pc = jax.lax.population_count(T).astype(jnp.int32)
        return jnp.sum(pc.reshape(S, nt_loc, tile), axis=(0, 2))

    def sweep_sparse(T, trans, allowed, idx, count):
        """Gather->expand->scatter over this SHARD's listed tiles (the
        caller builds the list from shard-local occupancy — or, with
        the seen memo, from the tiles that grew since last swept). Local
        slot bits mirror ops/wgl3_sparse.make_sparse_sweep on the shard;
        device-bit fires scatter to full shard width first, then cross
        the mesh with the same ppermute the dense expand uses.

        LOCKSTEP NOTE: keep the in-word/in-tile/tile-bit branches and
        the valid/src_ok masking identical to make_sparse_sweep (see its
        docstring) — fixes must land in both copies."""
        valid = cap_ids < count
        cols = idx[:, None] * tile + tile_off[None, :]
        flat = cols.reshape(-1)
        G = jnp.where(valid[None, :, None], T[:, cols], jnp.uint32(0))
        aG = allowed[cols][None]
        crossT = T
        for j in range(K):
            src = G & aG
            if j < 5:
                fired = or_reduce(trans[j], src & _LO_MASK[j])
                G = G | (fired << np.uint32(1 << j))
            elif j - 5 < tbits:
                lo_w = 1 << (j - 5)
                hi = tile >> (j - 4)
                Gr = G.reshape(S, CAP, hi, 2, lo_w)
                srcj = src.reshape(S, CAP, hi, 2, lo_w)[:, :, :, 0, :]
                fired = or_reduce(trans[j], srcj)
                G = jnp.stack(
                    [Gr[:, :, :, 0, :], Gr[:, :, :, 1, :] | fired],
                    axis=3).reshape(S, CAP, tile)
            elif j - 5 < lbits:
                # Local tile-index bit: scatter-OR into this shard.
                b = j - 5 - tbits
                src_ok = ((idx >> b) & 1) == 0
                fired = or_reduce(trans[j], src)
                fired = jnp.where((valid & src_ok)[None, :, None], fired,
                                  jnp.uint32(0))
                dcols = ((idx | (1 << b))[:, None] * tile
                         + tile_off[None, :]).reshape(-1)
                crossT = crossT | jnp.zeros_like(T).at[:, dcols].add(
                    fired.reshape(S, CAP * tile))
            else:
                # Device bit: fired configs scatter to full shard width,
                # then cross the mesh exactly like the dense expand.
                b = j - 5 - lbits
                src_dev = ((dev() >> b) & 1) == 0
                fired = or_reduce(trans[j], src)
                fired = jnp.where(valid[None, :, None] & src_dev, fired,
                                  jnp.uint32(0))
                fired_full = jnp.zeros_like(T).at[:, flat].add(
                    fired.reshape(S, CAP * tile))
                recv = jax.lax.ppermute(
                    fired_full, axis,
                    perm=[(p, p | (1 << b)) for p in range(d)
                          if not (p >> b) & 1])
                crossT = crossT | recv
        Gv = jnp.where(valid[None, :, None], G, jnp.uint32(0))
        return crossT | jnp.zeros_like(T).at[:, flat].add(
            Gv.reshape(S, CAP * tile))

    def step(carry, xs):
        T, dead, dead_step, maxf = carry
        if canon:
            trans, target, idx, pairs = xs
        else:
            trans, target, idx = xs
        is_pad = target < 0
        t = jnp.maximum(target, 0)
        allowed = allowed_mask(t)

        def body(st):
            if memo:
                (Tw, pc, n_prev, _c, rounds, sp_rounds, ovf_rounds,
                 swept) = st
            else:
                Tw, n_prev, _c, rounds, sp_rounds, ovf_rounds = st
            if plan is None:
                Tw = expand(Tw, trans, allowed)
                use_sparse = jnp.int32(0)
                ovf = jnp.int32(0)
            else:
                if memo:
                    occ_t = pc > 0
                    live_loc = jnp.sum(occ_t, dtype=jnp.int32)
                    elig_t = occ_t & (pc != swept)
                    elig_loc = jnp.sum(elig_t, dtype=jnp.int32)
                    elig_g = jax.lax.psum(elig_loc, axis)
                else:
                    occ_t, live_loc = occupancy(Tw)
                    elig_t, elig_loc = occ_t, live_loc
                    elig_g = None
                # All-reduced density signal: every device sees the same
                # global live count AND the worst shard's work-list
                # pressure, so the branch — and the ppermutes inside it —
                # is uniform across the mesh.
                live_g = jax.lax.psum(live_loc, axis)
                live_max = jax.lax.pmax(live_loc, axis)
                take_density = live_g <= thresh_glob
                take = take_density & (live_max <= CAP)
                # The previously-silent fallback, surfaced: a round the
                # density signal WANTED sparse but a shard's work-list
                # pressure forced dense (wgl.sparse_overflow_rounds).
                ovf = (take_density & ~take).astype(jnp.int32)
                wl = jnp.nonzero(elig_t, size=CAP, fill_value=0)[0]
                count = jnp.minimum(elig_loc, jnp.int32(CAP))
                if memo:
                    # Nothing grew anywhere on the mesh: the sweep is a
                    # no-op — skip it UNIFORMLY (the predicate is the
                    # psum'd count, so the collectives stay consistent).
                    take_sweep = take & (elig_g > 0)
                else:
                    take_sweep = take
                Tw = jax.lax.cond(
                    take_sweep,
                    lambda T: sweep_sparse(T, trans, allowed, wl, count),
                    lambda T: jax.lax.cond(
                        take, lambda T: T,
                        lambda T: expand(T, trans, allowed), T),
                    Tw)
                use_sparse = take.astype(jnp.int32)
                if memo:
                    swept2 = swept.at[
                        jnp.where(cap_ids < count, wl,
                                  jnp.int32(nt_loc))].set(
                            pc[wl], mode="drop")
                    swept = jnp.where(take, swept2,
                                      jnp.full((nt_loc,), -1, jnp.int32))
            if memo:
                # One shard-local reduce serves next round's eligibility
                # AND this round's psum'd convergence check.
                pc2 = tile_popcounts(Tw)
                n_now = jax.lax.psum(jnp.sum(pc2, dtype=jnp.int32), axis)
                return (Tw, pc2, n_now, n_now > n_prev, rounds + 1,
                        sp_rounds + use_sparse, ovf_rounds + ovf, swept)
            n_now = jax.lax.psum(
                jnp.sum(jax.lax.population_count(Tw), dtype=jnp.int32),
                axis)
            return (Tw, n_now, n_now > n_prev, rounds + 1,
                    sp_rounds + use_sparse, ovf_rounds + ovf)

        ci = 3 if memo else 2   # index of `changed` in the loop state

        def cond(st):
            return st[ci] & (st[ci + 1] < cfg.rounds)

        if memo:
            pc0 = tile_popcounts(T)
            init = (T, pc0,
                    jax.lax.psum(jnp.sum(pc0, dtype=jnp.int32), axis),
                    ~is_pad, jnp.int32(0), jnp.int32(0), jnp.int32(0),
                    jnp.full((nt_loc,), -1, jnp.int32))
            fin = jax.lax.while_loop(cond, body, init)
            T, _pc, n, _c, rounds, sp_rounds, ovf_rounds = fin[:7]
        else:
            n0 = jax.lax.psum(
                jnp.sum(jax.lax.population_count(T), dtype=jnp.int32),
                axis)
            init = (T, n0, ~is_pad, jnp.int32(0), jnp.int32(0),
                    jnp.int32(0))
            fin = jax.lax.while_loop(cond, body, init)
            T, n, _c, rounds, sp_rounds, ovf_rounds = fin[:6]
        if canon:
            # Shard-local canonicalization of the converged frontier
            # (pairs pre-filtered to shard-local bits by the caller);
            # the gate keys on the GLOBAL frontier size and the count
            # reduce is psum'd, so the branch — and the collective
            # inside it — is uniform across the mesh.
            T, n, canon_pruned, canon_base = apply_step_canon(
                canon_fn, T, pairs, n, is_pad, min_frontier,
                count_fn=lambda Tc: jax.lax.psum(
                    jnp.sum(jax.lax.population_count(Tc),
                            dtype=jnp.int32), axis))
        _occ, live_fin = occupancy(T)
        live_g_fin = jax.lax.psum(live_fin, axis)

        pruned = prune(T, t, allowed)
        T_new = jnp.where(is_pad, T, pruned)
        alive = jax.lax.psum(
            jnp.any(T_new != 0).astype(jnp.int32), axis) > 0
        died = ~is_pad & ~dead & ~alive
        dead = dead | died
        T_new = jnp.where(dead, jnp.zeros_like(T_new), T_new)
        sparse_all = ((~is_pad) & (rounds > 0)
                      & (sp_rounds == rounds)).astype(jnp.int32)
        outs = (jnp.where(is_pad, 0, n),
                jnp.where(is_pad, 0, live_g_fin),
                jnp.where(is_pad, 0, sparse_all),
                jnp.where(is_pad, 0, ovf_rounds))
        if canon:
            outs = outs + (canon_pruned, canon_base)
        return (T_new, dead,
                jnp.where(died & (dead_step < 0), idx, dead_step),
                jnp.maximum(maxf, n)), outs

    return step, w_loc, (tile, nt_glob)


def lattice_sparse_plan(cfg: DenseConfig, d: int):
    """The sparse plan for one SHARD of the lattice (None = dense): the
    work list and tile geometry are sized on the per-device word count,
    so each shard gathers its own live tiles."""
    from ..ops.wgl3_sparse import sparse_plan

    return sparse_plan(cfg, words=(1 << (cfg.k_slots - 5)) // d)


def make_lattice_chunk_fn(model: Model, cfg: DenseConfig, mesh: Mesh,
                          axis: str = "lattice", plan=None,
                          canon: bool = False, min_frontier: int = 0,
                          memo_slots: int = 0):
    """(jitted chunk fn, (tile_words, global n_tiles)): the chunk fn is
    (table[S, W] sharded, dead, dead_step, maxf, trans[C,K,S,S'],
    tgts[C], [pairs[C,P,2] when canon,] idx0) -> (table', dead',
    dead_step', maxf', f32[7] partials [configs, live-tile sum, real
    steps, sparse steps, overflow rounds, canon pruned, canon base —
    the canon columns are zeros in a canon-off build])
    — the sharded twin of wgl3._chunk_fn. The table stays a
    mesh-sharded jax.Array between host-loop chunks; the tiling rides
    along so the caller's sweep_summary denominator is EXACTLY the
    tiling the kernel swept. `axis` may be a tuple of mesh axis names
    (the N-D pod mesh: the word axis shards over the product, and
    every collective in the step reduces across both axes); default =
    every axis of `mesh`."""
    axis = _resolve_axis(mesh, axis)
    d = mesh_total(mesh)
    step, w_loc, tiling = _build_local_step(
        model, cfg, axis, d, plan=plan, canon=canon,
        min_frontier=min_frontier, memo_slots=memo_slots)

    def run(table, dead, dead_step, maxf, trans, tgts, *rest):
        if canon:
            pairs, idx0 = rest
        else:
            (idx0,) = rest
        idxs = idx0 + jnp.arange(tgts.shape[0], dtype=jnp.int32)
        xs = (trans, tgts, idxs) + ((pairs,) if canon else ())
        (table, dead, dead_step, maxf), outs = jax.lax.scan(
            step, (table, dead, dead_step, maxf), xs)
        # FIXED seven-column row in both builds (canon-off emits zero
        # canon columns): one partial layout, one consumer indexing.
        # jtflow: partials configs_explored,live_tile_sum,real_steps,sparse_steps,overflow_rounds,canon_pruned,canon_base
        parts = jnp.stack([
            jnp.sum(outs[0].astype(jnp.float32)),
            jnp.sum(outs[1].astype(jnp.float32)),
            jnp.sum((tgts >= 0).astype(jnp.float32)),
            jnp.sum(outs[2].astype(jnp.float32)),
            jnp.sum(outs[3].astype(jnp.float32)),
            jnp.sum(outs[4].astype(jnp.float32)) if canon
            else jnp.float32(0),
            jnp.sum(outs[5].astype(jnp.float32)) if canon
            else jnp.float32(0)])
        return table, dead, dead_step, maxf, parts

    in_specs = [P(None, axis), P(), P(), P(), P(None, None, None, None),
                P(None)]
    if canon:
        in_specs.append(P(None, None))   # pairs: replicated
    in_specs.append(P())
    specs = dict(
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(None, axis), P(), P(), P(), P()))
    sharded = shard_map(run, check_vma=False, **specs)
    # obs/ compile/execute attribution (the PR 1 invariant, enforced by
    # jtlint JTL105): this lane shipped uninstrumented in PR 3 — under
    # virtual-device CI it IS the production wide-geometry path.
    return instrument_kernel("wgl3-lattice-chunk", jax.jit(sharded)), \
        tiling


def cached_lattice_chunk(model: Model, cfg: DenseConfig, mesh: Mesh,
                         axis: str = "lattice", plan=None,
                         canon: bool = False, min_frontier: int = 0,
                         memo_slots: int = 0):
    axis = _resolve_axis(mesh, axis)
    key = ("lattice-chunk", model.cache_key(), cfg, _mesh_key(mesh), axis,
           plan, canon, min_frontier, memo_slots)
    if key not in _CACHE:
        _CACHE[key] = make_lattice_chunk_fn(
            model, cfg, mesh, axis, plan=plan, canon=canon,
            min_frontier=min_frontier, memo_slots=memo_slots)
    return _CACHE[key]


def _transitions_fn(model: Model, cfg: DenseConfig):
    key = ("lattice-trans", model.cache_key(), cfg)
    if key not in _CACHE:
        _, transitions = wgl3.make_step_fn3(model, cfg)
        _CACHE[key] = instrument_kernel("lattice-transitions",
                                        jax.jit(jax.vmap(transitions)))
    return _CACHE[key]


def check_steps_lattice_long(rs: ReturnSteps, model: Model,
                             cfg: DenseConfig, mesh: Mesh | None = None,
                             chunk: int | None = None,
                             time_budget_s: float | None = None) -> dict:
    """Sharded host-chunked dense sweep: the wide-geometry twin of
    wgl3.check_steps3_long. Same result schema, same honest "unknown" on
    budget expiry; exact otherwise. Eligible geometries run the sparse
    active-tile engine per shard (lattice_sparse_plan; limits().
    sparse_mode gates it) with the all-reduced density switch — this is
    the K ~ 20 lane the sparse engine exists for, so the win compounds
    with the device count."""
    import time as _time

    from ..ops.wgl import verdict
    from ..ops.wgl3 import sweep_summary

    t0 = _time.monotonic()
    if mesh is None:
        mesh = lattice_mesh()
    d = int(np.prod(list(mesh.shape.values())))
    plan = lattice_sparse_plan(cfg, d)
    if chunk is None:
        cells = cfg.n_states * cfg.n_masks // d   # per-device sweep cost
        base = limits().long_scan_chunk
        chunk = min(base, max(128, base * (1 << 15) // max(cells, 1)))
    n = rs.n_steps
    n_pad = (n + chunk - 1) // chunk * chunk
    rs = rs.padded_to(n_pad)
    # Frontier canonicalization (ops/canon.py): dedup SHARD-LOCALLY —
    # pairs touching device-index bits are filtered out host-side
    # (every compare-exchange is individually sound, so the partial
    # network is exact too), then the occupancy/density signals are
    # all-reduced exactly like the PR 3 sparse branch.
    from ..ops.canon import dedup_min_frontier_active, history_canon_pairs
    from ..ops.wgl3_sparse import memo_slots_for

    w_loc = (1 << (cfg.k_slots - 5)) // d
    pairs = history_canon_pairs(rs, table=True,
                                max_bit=5 + w_loc.bit_length() - 1)
    memo = memo_slots_for(plan) if plan is not None else 0
    run, tiling = cached_lattice_chunk(
        model, cfg, mesh, plan=plan, canon=pairs is not None,
        min_frontier=(dedup_min_frontier_active()
                      if pairs is not None else 0),
        memo_slots=memo)
    trans_of = _transitions_fn(model, cfg)
    # Carry starts as host values; jit output keeps the table sharded
    # across chunks.
    w = 1 << (cfg.k_slots - 5)
    table = jnp.zeros((cfg.n_states, w), jnp.uint32)
    row = int(model.init_state()) + cfg.state_offset
    table = table.at[row, 0].set(jnp.uint32(1))
    dead = jnp.bool_(False)
    dead_step = jnp.int32(-1)
    maxf = jnp.int32(1)
    cfgs_dev = None
    for c in range(n_pad // chunk):
        if (time_budget_s is not None
                and _time.monotonic() - t0 > time_budget_s):
            return {"valid": "unknown", "survived": False, "overflow": True,
                    "dead_step": -1, "max_frontier": -1,
                    "configs_explored": -1, "kernel": "exhausted",
                    "error": f"sharded dense sweep exceeded its "
                             f"{time_budget_s:.0f}s time budget at return "
                             f"step {c * chunk}"}
        sl = slice(c * chunk, (c + 1) * chunk)
        trans = trans_of(jnp.asarray(rs.slot_tabs[sl]),
                         jnp.asarray(rs.slot_active[sl]))
        args = (jnp.asarray(rs.targets[sl]),)
        if pairs is not None:
            args = args + (jnp.asarray(pairs[sl]),)
        table, dead, dead_step, maxf, part = run(
            table, dead, dead_step, maxf, trans, *args,
            jnp.int32(c * chunk))
        cfgs_dev = part if cfgs_dev is None else cfgs_dev + part
        # jtlint: disable=JTL103 -- per-chunk death fetch: chunk sizes here
        # are large (>=128 scanned steps each), so the fetch amortizes; it
        # is what bounds a falsified history's sweep to one extra chunk.
        if bool(np.asarray(dead)):
            break
    if cfgs_dev is None:
        cfgs_dev = jnp.zeros((7,), jnp.float32)
    # jtflow: partials-from lattice.make_lattice_chunk_fn
    parts = np.asarray(jnp.clip(cfgs_dev, 0, 2**31 - 1).astype(jnp.int32))
    out = {
        "survived": not bool(np.asarray(dead)),
        "overflow": False,
        "dead_step": int(np.asarray(dead_step)),
        "max_frontier": int(np.asarray(maxf)),
        "configs_explored": int(parts[0]),
        "kernel": ("wgl3-dense-lattice-sparse" if plan is not None
                   else "wgl3-dense-lattice-sharded"),
    }
    # Global sweep telemetry: the live counts were psum'd device-side
    # and `tiling` is exactly (tile_words, global tile count) the
    # compiled step swept — no recomputation to drift.
    out["sweep"] = sweep_summary(cfg, live_sum=float(parts[1]),
                                 real_steps=int(parts[2]),
                                 sparse_steps=int(parts[3]),
                                 tiling=tiling,
                                 overflow_rounds=int(parts[4]))
    out["live_tile_ratio"] = out["sweep"]["live_tile_ratio"]
    if pairs is not None:
        # Columns 5/6 are zeros in a canon-off build — only attach the
        # record when the canonicalizing kernel actually ran.
        wgl3.attach_dedup_record(out, pruned=float(parts[5]),
                                 base=float(parts[6]))
    out["valid"] = verdict(out)
    record_check_result(out)
    return out
