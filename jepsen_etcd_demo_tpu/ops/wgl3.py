"""WGL linearizability search v3: dense subset-lattice kernel.

The v1/v2 kernels (ops/wgl.py, ops/wgl2.py) keep the frontier as a compacted
LIST of (state, mask) configs and pay a sort-based dedup over
f_cap*(k_slots+1) keys per expansion round — the dominant cost on TPU, and
the reason round 1's bench lost to the CPU oracle. This kernel replaces the
list with the DENSE characteristic function of the frontier:

    table: bool[S, 2^K]   table[s, m] == "config (state s-offset, mask m)
                           is reachable"

where S bounds the model's reachable states (known host-side from the
history's values, models/base.py pack_bits rationale) and K = k_slots is the
pending-op slot count. This is viable exactly when S * 2^K is small — true
for every realistic jepsen history (concurrency ~10 ⇒ K ≈ 10-12, register
values ⇒ S ≈ 8), and decidable host-side (`dense_feasible`). Large-K
histories fall back to the sort kernel.

Why this is the TPU-native shape of the search:
  * dedup DISAPPEARS: the table is a canonical set representation; OR-ing
    candidates in is idempotent. No sort, no scatter, no compaction.
  * expanding "fire pending op j from every config" is, for the mask axis, a
    static reshape exposing bit j ([S, hi, 2, lo] with lo = 2^j) — the b=0
    half ORs into the b=1 half — and, for the state axis, a tiny [S,S]
    one-hot transition matmul (MXU food, S ≈ 8-64).
  * pruning at a return (keep configs that linearized the target, clear its
    bit) is ONE gather: table[:, m | (1<<t)] masked to bit-t-clear columns.
  * overflow CANNOT happen: the table holds the whole config space, so every
    verdict is exact — no capacity escalation, no oracle fallback
    (VERDICT.md round-1 item 4).

Search semantics are identical to v2 (and knossos :linear, reference call
site src/jepsen/etcdemo.clj:117): just-in-time linearization banks configs
that already fired the returning op (they are excluded as expansion sources
via the bit-t column mask), and the closure runs to fixpoint under a
lax.while_loop with a Gauss-Seidel sweep over slots (in-round chaining keeps
typical round counts at 1-2).

Consumes the same return-major encoding (encode.py ReturnSteps) as v2, so it
drops into the same scan/vmap/shard harness.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models.base import Model
from ..obs import (get_ledger, get_metrics, instrument_kernel,
                   record_check_result)
from .encode import EncodedHistory, ReturnSteps, encode_return_steps
from .limits import limits


@dataclass(frozen=True)
class DenseConfig:
    k_slots: int          # K: mask width; table mask axis is 2^K
    n_states: int         # S: table state axis (covers every reachable state)
    state_offset: int     # state value -> row index shift (NIL=-1 -> 0)
    max_rounds: int = 0   # closure sweep bound; default k_slots

    @property
    def n_masks(self) -> int:
        return 1 << self.k_slots

    @property
    def rounds(self) -> int:
        return self.max_rounds or self.k_slots


def dense_config(model: Model, k_slots: int, max_value: int,
                 budget: int | None = None) -> DenseConfig | None:
    """DenseConfig for this (model, history) — or None when infeasible.

    Feasible iff the model's states are boundable from the history's values
    (same precondition as the packed sort-key dedup), S <= 32 (the packed
    kernel unrolls its state OR-reduce), K >= 5 (the mask axis is packed 32
    configs per uint32 word), and the table fits the cell budget. S is
    rounded up (multiple of 4) so nearby value ranges share one jit cache
    entry, mirroring wgl2.make_config.

    The default budget (limits().dense_cell_budget) caps cells because
    per-step sweep cost is O(K * S * 2^K) regardless of how few configs
    are LIVE, while the sort kernel (wgl2) pays O(f_cap * K) — past
    K ~ 17 the live frontier is invariably tiny relative to the lattice,
    so dense sweeps waste >100x the work; 2^20 cells admits typical
    jepsen geometries (concurrency 10 gives K=12, a 4 KiB table) and
    routes wider ones to wgl2 (or the sharded lattice, parallel/)."""
    if budget is None:
        budget = limits().dense_cell_budget
    if not model.packable_states or k_slots < 5:
        return None
    s = model.state_bound(max_value) + 1
    s = (s + 3) // 4 * 4
    if s > 32 or s * (1 << k_slots) > budget:
        return None
    return DenseConfig(k_slots=k_slots, n_states=s,
                       state_offset=model.state_offset)


class _Carry3(NamedTuple):
    table: jax.Array        # u32[S, W]: bit b of word w = mask (w*32 + b)
    dead: jax.Array         # bool
    dead_step: jax.Array    # i32 (return-step index, -1 if alive)
    max_frontier: jax.Array  # i32 (popcount high-water mark)


# LO_MASK[j] (j < 5): bits p in 0..31 whose index has bit j CLEAR — the
# in-word "mask bit j not yet fired" positions. 32 = 2^5 configs pack
# per u32 word: every `1 << (K - 5)` table-width computation here and in
# wgl3_sparse/wgl3_pallas/parallel.lattice derives from THIS packing —
# the jtflow pass (JTL403) pins their shift literals to it.
# jtflow: table-word-bits=5
_LO_MASK = tuple(
    np.uint32(sum(1 << p for p in range(32) if not (p >> j) & 1))
    for j in range(5))


class _TableOps(NamedTuple):
    """The bit-algebra building blocks of the dense lattice sweep, shared
    by the dense step fn (make_step_fn3), the sparse active-tile engine
    (ops/wgl3_sparse.py), and the lattice-sharded form (parallel/
    lattice.py builds its own shard-local variants of the same ops)."""
    allowed_mask: Any       # t -> u32[W] (mask-bit-t-CLEAR positions)
    or_reduce: Any          # ([S,S'] trans, u32[S,...]) -> u32[S,...]
    transitions: Any        # (slot_tab[K,4], slot_active[K]) -> [K,S,S']
    dense_sweep: Any        # (T, allowed, trans) -> T — one G-S round
    prune: Any              # (T, t, allowed) -> pruned table


def table_ops(model: Model, cfg: DenseConfig) -> _TableOps:
    """Build the per-geometry table operations (see _TableOps).

    The mask axis is packed 32 configs/word: masks' low 5 bits index bits
    inside a uint32, the high K-5 bits index words. Every set operation
    becomes word-wise bit algebra (32x less memory traffic than a bool
    table, no bool->f32 conversions, no MXU needed):
      * expanding slot j<5  = in-word shift:  (src & LO_MASK[j]) << 2^j
      * expanding slot j>=5 = word-axis reshape exposing word-bit j-5
      * state transition    = OR-reduce over source states (S unrolled,
        S <= 32 guaranteed by dense_config)
      * pruning at return t = word gather + in-word shift, then mask
      * frontier size       = population_count
    """
    K, S, off = cfg.k_slots, cfg.n_states, cfg.state_offset
    assert K >= 5 and S <= 32
    W = 1 << (K - 5)
    state_vals = jnp.arange(S, dtype=jnp.int32) - off
    s_ids = jnp.arange(S, dtype=jnp.int32)
    w_idx = jnp.arange(W, dtype=jnp.int32)
    lo_masks = jnp.asarray(np.array(_LO_MASK, dtype=np.uint32))
    full = jnp.uint32(0xFFFFFFFF)

    def allowed_mask(t):
        """uint32[W]: per-word mask of config positions with mask-bit t
        CLEAR (not-yet-fired-t). Serves both banking and prune."""
        in_word = lo_masks[jnp.minimum(t, 4)]
        word_level = jnp.where(
            ((w_idx >> jnp.maximum(t - 5, 0)) & 1) == 0, full, jnp.uint32(0))
        return jnp.where(t < 5, jnp.broadcast_to(in_word, (W,)), word_level)

    def or_reduce(tj, src):
        """OR over source states: out[s', ...] = OR_s tj[s, s'] & src[s].
        S is small (<=32): unrolled selects, no matmul."""
        acc = jnp.zeros_like(src)
        for s in range(S):
            sel = tj[s].reshape((S,) + (1,) * (src.ndim - 1))
            acc = acc | jnp.where(sel, src[s][None], jnp.uint32(0))
        return acc

    def transitions(slot_tab, slot_active):
        """Per-slot transition matrices over the state axis: [K, S, S'].
        Pure function of the scan inputs — computed for ALL steps in one
        vectorized shot before the scan (keeps the sequential per-step
        critical path to pure bit algebra)."""
        legal, nxt = jax.vmap(
            lambda row: model.step(state_vals, row[0], row[1], row[2],
                                   row[3]))(slot_tab)
        nxt_row = nxt + off
        ok = legal & (nxt_row >= 0) & (nxt_row < S) & slot_active[:, None]
        return (ok[:, :, None]
                & (nxt_row[:, :, None] == s_ids[None, None, :]))

    def dense_sweep(T, allowed, trans):
        """One Gauss-Seidel sweep: fire each slot once, updating T in
        place so same-round chains propagate. Static python loop — K is
        small and each j needs its own static bit/word addressing."""
        for j in range(K):
            src = T & allowed[None, :]
            if j < 5:
                fired = or_reduce(trans[j], src & _LO_MASK[j])
                T = T | (fired << np.uint32(1 << j))
            else:
                lo_w, hi = 1 << (j - 5), W >> (j - 4)
                Tr = T.reshape(S, hi, 2, lo_w)
                srcj = src.reshape(S, hi, 2, lo_w)[:, :, 0, :]
                fired = or_reduce(trans[j], srcj)
                T = jnp.stack([Tr[:, :, 0, :], Tr[:, :, 1, :] | fired],
                              axis=2).reshape(S, W)
        return T

    def prune(T, t, allowed):
        """Keep configs that linearized the target, re-addressed with its
        bit cleared. t<5: in-word shift down; t>=5: word gather."""
        shift = jnp.where(t < 5, jnp.uint32(1) << jnp.minimum(
            t.astype(jnp.uint32), jnp.uint32(4)), jnp.uint32(0))
        wsel = jnp.where(t < 5, w_idx,
                         w_idx | (jnp.int32(1) << jnp.maximum(t - 5, 0)))
        return (T[:, wsel] >> shift) & allowed[None, :]

    return _TableOps(allowed_mask=allowed_mask, or_reduce=or_reduce,
                     transitions=transitions, dense_sweep=dense_sweep,
                     prune=prune)


def live_tile_geometry(cfg: DenseConfig,
                       words: int | None = None) -> tuple[int, int]:
    """(tile_words, n_tiles) of the occupancy tiling for this geometry:
    limits().sparse_tile_words clamped (and rounded down to a power of
    two) against the table width — `words` overrides the width for
    SHARDED tables (per-device word count). THE single copy of the
    tiling policy: the sparse engine (ops/wgl3_sparse.sparse_plan), the
    lattice shard tiling (parallel/lattice.py), and the live-tile-ratio
    telemetry all derive from here, so the gauge's denominator and the
    sweep's actual work unit cannot disagree."""
    w = words if words is not None else (1 << (cfg.k_slots - 5))
    tile = max(1, min(limits().sparse_tile_words, w))
    if tile & (tile - 1):
        tile = 1 << (tile.bit_length() - 1)
    return tile, w // tile


def make_step_fn3(model: Model, cfg: DenseConfig, canon: bool = False,
                  min_frontier: int = 0):
    """Scan body over the bit-packed table (see table_ops for the bit
    algebra). Each step additionally emits the converged table's live-
    TILE count (occupancy over live_tile_geometry tiles) — the telemetry
    behind the wgl.live_tile_ratio gauge and the sparse engine's density
    signal (ops/wgl3_sparse.py); one O(S*W) reduce per step, ~1/K of a
    single sweep's cost.

    With ``canon=True`` the scan inputs gain a per-step compare-exchange
    network (ops/canon.py canon_pairs) and each step canonicalizes the
    CONVERGED frontier before metrics and prune — symmetry-reducing
    equal-effect forever-pending ops, a verdict-preserving quotient (the
    soundness argument lives in ops/canon.py). The step then emits two
    extra outputs (configs pruned by canonicalization, the pre-canon
    count at canon-applied steps). ``min_frontier`` skips the pass on
    converged frontiers below it (always sound; dedup_mode=2 passes 0).
    The default build is byte-identical to the pre-dedup kernel."""
    ops = table_ops(model, cfg)
    allowed_mask, transitions = ops.allowed_mask, ops.transitions
    tile, n_tiles = live_tile_geometry(cfg)
    if canon:
        from .canon import apply_step_canon, make_table_canon

        canon_fn = make_table_canon(1 << (cfg.k_slots - 5))

    def live_tiles(T):
        any_w = jnp.any(T != jnp.uint32(0), axis=0)
        return jnp.sum(jnp.any(any_w.reshape(n_tiles, tile), axis=1),
                       dtype=jnp.int32)

    def step(carry: _Carry3, xs):
        if canon:
            trans, target, idx, pairs = xs
        else:
            trans, target, idx = xs
        is_pad = target < 0
        t = jnp.maximum(target, 0)

        # JIT-linearization banking: configs that already fired the target
        # are kept but never expanded.
        allowed = allowed_mask(t)                            # u32[W]

        def body(st):
            T, n_prev, _changed, rounds = st
            T = ops.dense_sweep(T, allowed, trans)
            n_now = jnp.sum(jax.lax.population_count(T), dtype=jnp.int32)
            return T, n_now, n_now > n_prev, rounds + 1

        def cond(st):
            return st[2] & (st[3] < cfg.rounds)

        n0 = jnp.sum(jax.lax.population_count(carry.table), dtype=jnp.int32)
        T, n, _c, _r = jax.lax.while_loop(
            cond, body, (carry.table, n0, ~is_pad, jnp.int32(0)))

        if canon:
            # Canonicalize the converged frontier BEFORE metrics and
            # prune: max_frontier / configs_explored count UNIQUE
            # (canonical) configs, and the occupancy the sparse signal
            # sees is the reduced one (apply_step_canon gates the pass
            # so quiet steps pay nothing).
            T, n, canon_pruned, canon_base = apply_step_canon(
                canon_fn, T, pairs, n, is_pad, min_frontier)
        live = live_tiles(T)
        pruned = ops.prune(T, t, allowed)
        T_new = jnp.where(is_pad, T, pruned)
        alive = jnp.any(T_new != 0)
        died = ~is_pad & ~carry.dead & ~alive
        dead = carry.dead | died
        T_new = jnp.where(dead, jnp.zeros_like(T_new), T_new)
        outs = (jnp.where(is_pad, 0, n),
                jnp.where(is_pad, 0, live))
        #       pads do no search work: keep the configs-explored and
        #       live-tile metrics padding-invariant (scan buckets here,
        #       chunk alignment in the pallas kernel — both must agree
        #       exactly)
        if canon:
            outs = outs + (canon_pruned, canon_base)
        return _Carry3(
            table=T_new, dead=dead,
            dead_step=jnp.where(died & (carry.dead_step < 0), idx,
                                carry.dead_step),
            max_frontier=jnp.maximum(carry.max_frontier, n)), outs

    return step, transitions


def _init_carry3(model: Model, cfg: DenseConfig) -> _Carry3:
    row = int(model.init_state()) + cfg.state_offset
    table = jnp.zeros((cfg.n_states, 1 << (cfg.k_slots - 5)), jnp.uint32
                      ).at[row, 0].set(jnp.uint32(1))
    return _Carry3(table=table, dead=jnp.bool_(False),
                   dead_step=jnp.int32(-1), max_frontier=jnp.int32(1))


def _check_one_fn(model: Model, cfg: DenseConfig):
    step, transitions = make_step_fn3(model, cfg)
    _, n_tiles = live_tile_geometry(cfg)

    def check(slot_tabs, slot_active, targets):
        carry = _init_carry3(model, cfg)
        idxs = jnp.arange(targets.shape[0], dtype=jnp.int32)
        trans_all = jax.vmap(transitions)(slot_tabs, slot_active)
        final, (ns, lives) = jax.lax.scan(
            step, carry, (trans_all, targets, idxs))
        real = jnp.sum((targets >= 0).astype(jnp.int32))
        # Mean live-tile occupancy over real steps, in per-mille (i32 so
        # it packs with the verdict fields): the telemetry behind the
        # wgl.live_tile_ratio gauge and the sparse engine's motivation —
        # -1 when the history had no real steps.
        live_pm = jnp.where(
            real > 0,
            (jnp.sum(lives.astype(jnp.float32)) * 1000.0
             / (jnp.maximum(real, 1).astype(jnp.float32) * n_tiles)
             ).astype(jnp.int32),
            jnp.int32(-1))
        return {
            "survived": ~final.dead,
            # The dense table is the whole config space: exact by
            # construction. Constant False keeps the v2 result schema (and
            # wgl.verdict's tri-state logic) unchanged.
            "overflow": jnp.bool_(False),
            "dead_step": final.dead_step,
            "max_frontier": final.max_frontier,
            # §5.1 checker metric: total configs live across all return
            # steps (the kernel's unit of search work; configs/sec = this
            # over wall time). f32 accumulator: x64 is disabled under jit
            # and a throughput metric tolerates rounding past 2^24.
            "configs_explored": jnp.sum(ns.astype(jnp.float32)),
            "live_tile_pm": live_pm,
        }

    return check


def make_checker3(model: Model, cfg: DenseConfig):
    """jitted check(slot_tabs[R,K,4], slot_active[R,K], targets[R])."""
    return jax.jit(_check_one_fn(model, cfg))


def _chunk_fn(model: Model, cfg: DenseConfig):
    """jitted (carry, tabs[C,K,4], act[C,K], tgts[C], idx0) ->
    (carry', configs-partial f32 scalar) — the partial sums accumulate
    device-side across chunks and are fetched once at the end. The carry
    is DONATED: every caller threads it linearly (chunk N's output is
    chunk N+1's input and nothing else reads the old buffer), so XLA can
    alias the table in place instead of allocating a fresh one per
    chunk."""
    step, transitions = make_step_fn3(model, cfg)

    def run(carry, tabs, act, tgts, idx0):
        trans = jax.vmap(transitions)(tabs, act)
        idxs = idx0 + jnp.arange(tgts.shape[0], dtype=jnp.int32)
        carry, (ns, lives) = jax.lax.scan(step, carry, (trans, tgts, idxs))
        # Partial sums accumulate device-side across chunks, fetched once
        # at the end — the row layout every chunk consumer (the long
        # sweep below, stream/engine.py finalize) indexes into.
        # jtflow: partials configs_explored,live_tile_sum,real_steps
        return carry, jnp.stack([
            jnp.sum(ns.astype(jnp.float32)),
            jnp.sum(lives.astype(jnp.float32)),
            jnp.sum((tgts >= 0).astype(jnp.float32))])

    return jax.jit(run, donate_argnums=(0,))


def _chunk_fn_dedup(model: Model, cfg: DenseConfig, min_frontier: int):
    """Canonicalizing twin of _chunk_fn: the scan inputs gain the
    per-step exchange network (pairs i32[C, P, 2]) and the partial row
    grows the dedup accounting — configs pruned by canonicalization and
    the pre-canon config count at canon-applied steps (the
    frontier_dedup_ratio denominator). Built ONLY for histories whose
    network is non-empty (canon_pairs returned rows), so the default
    path's compiled shapes never change."""
    step, transitions = make_step_fn3(model, cfg, canon=True,
                                      min_frontier=min_frontier)

    def run(carry, tabs, act, tgts, pairs, idx0):
        trans = jax.vmap(transitions)(tabs, act)
        idxs = idx0 + jnp.arange(tgts.shape[0], dtype=jnp.int32)
        carry, (ns, lives, pruned, base) = jax.lax.scan(
            step, carry, (trans, tgts, idxs, pairs))
        # jtflow: partials configs_explored,live_tile_sum,real_steps,canon_pruned,canon_base
        return carry, jnp.stack([
            jnp.sum(ns.astype(jnp.float32)),
            jnp.sum(lives.astype(jnp.float32)),
            jnp.sum((tgts >= 0).astype(jnp.float32)),
            jnp.sum(pruned.astype(jnp.float32)),
            jnp.sum(base.astype(jnp.float32))])

    return jax.jit(run, donate_argnums=(0,))


def dedup_min_frontier_active(lim=None) -> int:
    """Lazy alias of ops/canon.dedup_min_frontier_active (the policy's
    one copy lives there; the alias keeps wgl3 the import point for the
    sweep-side consumers without a module-level canon import, which
    would be circular)."""
    from .canon import dedup_min_frontier_active as _f

    return _f(lim)


def history_canon_pairs(rs: ReturnSteps, max_bit: int | None = None,
                        table: bool = False):
    """Lazy alias of ops/canon.history_canon_pairs — the ONE copy of
    the dedup engage policy (see its docstring for the auto/force
    scoping rationale)."""
    from .canon import history_canon_pairs as _f

    return _f(rs, max_bit=max_bit, table=table)


def attach_dedup_record(out: dict, pruned: float, base: float) -> None:
    """Fold the chunked partials' canonicalization accounting into the
    result dict: configs pruned, the pre-canon base count, and the
    frontier_dedup_ratio (pruned/base over canon-applied steps) behind
    the wgl.configs_pruned counter and wgl.frontier_dedup_ratio gauge
    (obs.record_check_result). ONE copy shared by the dense, sparse,
    and lattice long sweeps."""
    pruned = max(0, int(pruned))
    base = max(0, int(base))
    out["dedup"] = {
        "configs_pruned": pruned,
        "canon_base": base,
        "frontier_dedup_ratio": round(pruned / base, 4) if base else 0.0,
    }


def default_scan_chunk(cfg: DenseConfig) -> int:
    """Host-loop chunk size: scales inversely with table width (sweep cost
    per step is proportional to cells). Floor 128: at the chunked-budget
    cell ceiling a step costs ~70 ms, so even the floor chunk stays ~10 s
    — safely under the worker's program-kill threshold. ONE copy shared by
    the long sweep and witness frontier recovery so a tuning change can't
    leave one of them outside the envelope."""
    cells = cfg.n_states * cfg.n_masks
    base = limits().long_scan_chunk
    return min(base, max(128, base * (1 << 15) // max(cells, 1)))


def _cached_chunk_run(model: Model, cfg: DenseConfig, chunk: int):
    key = ("chunk3", model.cache_key(), cfg, chunk)
    if key not in _CACHE:
        # instrument_kernel (obs/): compile/execute attribution, one
        # first-call flag per compiled geometry (this cache's key).
        _CACHE[key] = instrument_kernel("wgl3-chunk", _chunk_fn(model, cfg))
    return _CACHE[key]


def _cached_chunk_run_dedup(model: Model, cfg: DenseConfig, chunk: int,
                            min_frontier: int):
    key = ("chunk3-dedup", model.cache_key(), cfg, chunk, min_frontier)
    if key not in _CACHE:
        _CACHE[key] = instrument_kernel(
            "wgl3-chunk-dedup", _chunk_fn_dedup(model, cfg, min_frontier))
    return _CACHE[key]


def sweep_summary(cfg: DenseConfig, live_sum: float, real_steps: int,
                  sparse_steps: int = 0,
                  tiling: tuple[int, int] | None = None,
                  overflow_rounds: int = 0) -> dict:
    """The per-run sweep-mode/occupancy record the long sweeps attach to
    their result dicts (and record_check_result folds into the metrics
    registry): which sweep mode the steps ran under and the mean live-
    tile ratio of the converged tables. One copy shared by the dense and
    sparse long sweeps (and the lattice-sharded form, which passes its
    own (tile_words, global tile count) `tiling`) so the bench's
    `sparse` lane and the telemetry artifact cannot drift apart."""
    tile, n_tiles = tiling if tiling is not None else live_tile_geometry(cfg)
    real = max(0, int(real_steps))
    sparse = min(max(0, int(sparse_steps)), real)
    dense = real - sparse
    if real == 0 or sparse == 0:
        mode = "dense"
    elif dense == 0:
        mode = "sparse"
    else:
        mode = "mixed"
    ratio = (float(live_sum) / (real * n_tiles)) if real else 0.0
    return {"mode": mode,
            "live_tile_ratio": round(min(max(ratio, 0.0), 1.0), 4),
            "steps_sparse": sparse, "steps_dense": dense,
            "tiles": n_tiles, "tile_words": tile,
            # Work-list overflows that forced a dense closure round
            # (ops/wgl3_sparse.py — the previously-silent fallback,
            # surfaced as the wgl.sparse_overflow_rounds counter).
            "overflow_rounds": max(0, int(overflow_rounds))}


def check_steps3_long(rs: ReturnSteps, model: Model, cfg: DenseConfig,
                      chunk: int | None = None,
                      time_budget_s: float | None = None,
                      spill_tag: str | None = None) -> dict:
    """Single-history dense check for histories whose step count exceeds
    one scan program: pad to a chunk multiple, loop chunks host-side.
    Bit-identical to check_steps3 (same step fn; pads contribute nothing).

    Geometries with enough occupancy tiles route to the sparse
    active-tile engine (ops/wgl3_sparse.py — limits().sparse_mode gates
    it): same chunked host loop, but each closure round gathers only the
    LIVE tiles of the table and falls back to a dense sweep past the
    density threshold, so per-step cost tracks the live frontier instead
    of 2^K. Verdicts are bit-identical either way (the sparse round
    reaches the same closure fixpoint).

    Chunk size scales inversely with table width so one chunk's wall time
    stays short (sweep cost per step is proportional to the cell
    count). `time_budget_s` bounds
    wall time between chunks; expiry returns the honest tri-state
    "unknown" with overflow=True (same contract as the sort ladder,
    ops/wgl2.py).

    Without a budget the chunk loop is PIPELINED (sched/pipeline.py):
    chunk N+1's slices transfer while chunk N executes (async dispatch —
    the carry chains device-side, with the frontier buffer donated), and
    the death-poll fetch happens only every limits().sched_poll_chunks
    chunks instead of per chunk — dead chunks in between are near-free
    (the closure exits immediately on an empty table) and death-sticky
    carries keep dead_step/max_frontier exact, so the result is
    bit-identical to the per-chunk loop. The budgeted path stays
    synchronous per chunk: the budget check must see device time.

    `spill_tag` (with an active store/spill.py SpillDir and the
    host_spill_mode policy engaged) spills the packed table at chunk
    seams — the death-poll cadence, so the explicit host fetch the
    DONATED carry requires rides the same sync the poll already pays —
    and resumes from a matching checkpoint on re-entry. A torn or
    mismatched checkpoint degrades to recompute from the start, never
    a wrong verdict. The sparse-engine route ignores the tag (its
    carry is gathered, not a whole table)."""
    import time as _time

    from ..sched.pipeline import double_buffer
    from .wgl3_sparse import check_steps3_long_sparse, sparse_plan

    plan = sparse_plan(cfg)
    if plan is not None:
        return check_steps3_long_sparse(rs, model, cfg, plan, chunk=chunk,
                                        time_budget_s=time_budget_s)
    t0 = _time.monotonic()
    if chunk is None:
        chunk = default_scan_chunk(cfg)
    n = rs.n_steps
    n_pad = (n + chunk - 1) // chunk * chunk
    rs = rs.padded_to(n_pad)
    # Frontier canonicalization (ops/canon.py): when the history carries
    # equal-effect forever-pending ops (and dedup_mode allows), the scan
    # threads the per-step exchange network and every step symmetry-
    # reduces the converged frontier. Histories with no symmetry — the
    # common case — take the byte-identical pre-dedup chunk fn.
    pairs = history_canon_pairs(rs, table=True)
    if pairs is not None:
        run = _cached_chunk_run_dedup(model, cfg, chunk,
                                      dedup_min_frontier_active())
    else:
        run = _cached_chunk_run(model, cfg, chunk)
    carry = _init_carry3(model, cfg)
    cfgs_dev = None
    # Out-of-core seam checkpoints (ISSUE 20): engaged only with an
    # active SpillDir, a caller tag, and the host_spill_mode policy
    # saying yes for this history's host working set.
    from ..store import spill as _spill

    sdir = _spill.active_spill() if spill_tag is not None else None
    do_spill = False
    ck_name = None
    start_c = 0
    n_words = 1 << (cfg.k_slots - 5)
    if sdir is not None:
        est_mb = (rs.slot_tabs.nbytes + rs.slot_active.nbytes
                  + rs.targets.nbytes) / (1 << 20)
        do_spill = _spill.spill_active(est_mb)
    if do_spill:
        ck_name = f"{spill_tag}.ck3"
        d = _spill.load_frontier(sdir, ck_name)
        mt = (d or {}).get("meta") or {}
        if d is not None and mt.get("n_steps") == n_pad \
                and mt.get("chunk") == chunk \
                and mt.get("shape") == [cfg.n_states, n_words] \
                and 0 < int(mt.get("pos", 0)):
            # Resume from the spilled seam checkpoint (only live seams
            # are spilled, so dead/dead_step reset is exact).
            carry = _Carry3(table=jnp.asarray(d["masks"]),
                            dead=jnp.bool_(False),
                            dead_step=jnp.int32(-1),
                            max_frontier=jnp.int32(
                                int(mt.get("max_frontier", 1))))
            if mt.get("cfgs") is not None:
                cfgs_dev = jnp.asarray(
                    np.asarray(mt["cfgs"], np.float32))
            start_c = int(mt["pos"])

    def seam_spill(done_c: int) -> None:
        # The chunk fn DONATES its carry, so the seam checkpoint pays
        # an explicit host fetch — scheduled at the death-poll cadence,
        # where the pipeline already syncs. Raw codec route: the packed
        # table is not per-config class bits, but a sparse table is
        # mostly zero words and the frame compresses it anyway.
        tbl = np.asarray(carry.table)
        cf = None if cfgs_dev is None \
            else [float(x) for x in np.asarray(cfgs_dev)]
        _spill.spill_frontier(
            sdir, ck_name, np.arange(tbl.shape[0], dtype=np.int32),
            tbl, np.ones(tbl.shape[0], bool),
            meta={"pos": done_c, "n_steps": n_pad, "chunk": chunk,
                  "shape": [int(tbl.shape[0]), int(tbl.shape[1])],
                  "max_frontier": int(np.asarray(carry.max_frontier)),
                  "cfgs": cf})

    if time_budget_s is None:
        poll = max(1, limits().sched_poll_chunks)

        def stage(c):
            sl = slice(c * chunk, (c + 1) * chunk)
            staged = (jnp.asarray(rs.slot_tabs[sl]),
                      jnp.asarray(rs.slot_active[sl]),
                      jnp.asarray(rs.targets[sl]))
            if pairs is not None:
                staged = staged + (jnp.asarray(pairs[sl]),)
            return staged + (jnp.int32(c * chunk),)

        done = 0
        for staged in double_buffer(range(start_c, n_pad // chunk),
                                    stage):
            carry, part = run(carry, *staged)
            cfgs_dev = part if cfgs_dev is None else cfgs_dev + part
            done += 1
            if done % poll == 0:
                # jtlint: disable=JTL103 -- bounded death poll: one
                # fetch per sched_poll_chunks chunks (the [tunable]
                # knob), not per iteration — the doc/perf.md early-exit
                # contract; the seam spill rides the same sync.
                if bool(np.asarray(carry.dead)):
                    break
                if do_spill:
                    seam_spill(start_c + done)
    else:
        for c in range(start_c, n_pad // chunk):
            if _time.monotonic() - t0 > time_budget_s:
                return {"valid": "unknown", "survived": False,
                        "overflow": True, "dead_step": -1,
                        "max_frontier": -1, "configs_explored": -1,
                        "kernel": "exhausted",
                        "error": f"dense-chunked sweep exceeded its "
                                 f"{time_budget_s:.0f}s time budget at "
                                 f"return step {c * chunk}"}
            sl = slice(c * chunk, (c + 1) * chunk)
            args = (jnp.asarray(rs.slot_tabs[sl]),
                    jnp.asarray(rs.slot_active[sl]),
                    jnp.asarray(rs.targets[sl]))
            if pairs is not None:
                args = args + (jnp.asarray(pairs[sl]),)
            carry, part = run(carry, *args, jnp.int32(c * chunk))
            cfgs_dev = part if cfgs_dev is None else cfgs_dev + part
            # Early exit on death: one 1-byte fetch per chunk vs
            # minutes of dead chunks on wide tables.
            # jtlint: disable=JTL103 -- budgeted lane is synchronous BY
            # CONTRACT: the budget check must see device time, so the
            # per-chunk fetch is the bound on overshoot.
            if bool(np.asarray(carry.dead)):
                break
            if do_spill:
                seam_spill(c + 1)
    from .wgl import verdict

    n_parts = 5 if pairs is not None else 3
    if cfgs_dev is None:
        cfgs_dev = jnp.zeros((n_parts,), jnp.float32)
    # One packed fetch at the end (chunks chain device-side): 3 verdict
    # fields + the chunk fn's declared partial row.
    # jtflow: partials-from wgl3._chunk_fn
    # jtflow: partials-from wgl3._chunk_fn_dedup
    packed = np.asarray(jnp.concatenate([
        jnp.stack([jnp.where(carry.dead, 0, 1),
                   carry.dead_step, carry.max_frontier]),
        jnp.clip(cfgs_dev, 0, 2**31 - 1).astype(jnp.int32)]))
    out = {
        "survived": bool(packed[0]),
        "overflow": False,
        "dead_step": int(packed[1]),
        "max_frontier": int(packed[2]),
        "configs_explored": int(packed[3]),
    }
    out["sweep"] = sweep_summary(cfg, live_sum=float(packed[4]),
                                 real_steps=int(packed[5]))
    out["live_tile_ratio"] = out["sweep"]["live_tile_ratio"]
    if pairs is not None:
        # The canon columns are the LAST two of the dedup layout by
        # construction (wgl3._chunk_fn_dedup) — negative indexing keeps
        # the base-layout reads above layout-checkable (JTL401).
        attach_dedup_record(out, pruned=float(packed[-2]),
                            base=float(packed[-1]))
    out["valid"] = verdict(out)
    record_check_result(out)
    return out


def recover_table3(rs: ReturnSteps, model: Model, cfg: DenseConfig,
                   upto_step: int,
                   chunk: int | None = None) -> list[tuple[int, int]]:
    """EXACT reachable-config set after the first `upto_step` return steps:
    run the chunked dense scan that far, fetch the table once, decode the
    set bits host-side. Returns [(state_value, linearized-mask), ...].

    This is the frontier-recovery half of big-history witness extraction
    (checkers/witness.py): the kernel knows WHERE a search died
    (dead_step) but keeps no lineage; recovering the frontier shortly
    before the death point lets the host replay only a bounded window
    instead of the whole exponential prefix."""
    if chunk is None:
        chunk = default_scan_chunk(cfg)
    run = _cached_chunk_run(model, cfg, chunk)
    upto = min(upto_step, rs.n_steps)
    # Truncate to the prefix, then pad the tail chunk with -1 targets
    # (pad steps leave the table untouched).
    n_pad = max(1, (upto + chunk - 1) // chunk) * chunk
    pre = ReturnSteps(rs.slot_tabs[:upto], rs.slot_active[:upto],
                      rs.targets[:upto], upto, rs.n_ops, rs.k_slots,
                      rs.max_pending, rs.max_value).padded_to(n_pad)
    carry = _init_carry3(model, cfg)
    for c in range(n_pad // chunk):
        sl = slice(c * chunk, (c + 1) * chunk)
        carry, _ = run(carry, jnp.asarray(pre.slot_tabs[sl]),
                       jnp.asarray(pre.slot_active[sl]),
                       jnp.asarray(pre.targets[sl]),
                       jnp.int32(c * chunk))
    table = np.asarray(carry.table)            # u32[S, W]
    configs = []
    for srow in range(cfg.n_states):
        for w in np.nonzero(table[srow])[0]:
            bits = int(table[srow, w])
            while bits:
                b = bits & -bits
                configs.append((srow - cfg.state_offset,
                                int(w) * 32 + b.bit_length() - 1))
                bits ^= b
    return configs


def make_batch_checker3(model: Model, cfg: DenseConfig):
    """jitted check over a batch: slot_tabs[B,R,K,4], ... -> [B] results."""
    return jax.jit(jax.vmap(_check_one_fn(model, cfg)))


# -- packed results ------------------------------------------------------
# One device->host fetch per launch: the result dict is stacked into a
# single i32[..., 5] tensor on device and split on host: every fetch
# pays a device->host round trip, and five small fetches cost more than
# one packed one.

PACKED_FIELDS = ("survived", "overflow", "dead_step", "max_frontier",
                 "configs_explored")
# The XLA checkers append a 6th telemetry column: mean live-tile
# occupancy in per-mille (live_tile_pm; -1 = not measured). The pallas
# kernels keep the 5-column layout — unpack_np accepts both widths, so
# the two backends' fetch contract stays one packed i32 tensor.
PACKED_FIELDS_XLA = PACKED_FIELDS + ("live_tile_pm",)


# jtflow: packs wgl3.PACKED_FIELDS_XLA
def _pack_result(out: dict) -> jax.Array:
    cfgs = jnp.clip(out["configs_explored"], 0, 2**31 - 1).astype(jnp.int32)
    return jnp.stack([out["survived"].astype(jnp.int32),
                      out["overflow"].astype(jnp.int32),
                      out["dead_step"], out["max_frontier"], cfgs,
                      out["live_tile_pm"]], axis=-1)


# jtflow: unpacks wgl3.PACKED_FIELDS_XLA
def unpack_np(arr) -> dict:
    """np i32[..., 5|6] (one fetch) -> result dict of np arrays/scalars.
    The 6th column (live_tile_pm), when present, is the XLA checkers'
    occupancy telemetry; pallas launches emit 5 columns and report -1."""
    arr = np.asarray(arr)
    get_metrics().counter("wgl.d2h_bytes").add(int(arr.nbytes))
    pm = (arr[..., 5] if arr.shape[-1] > 5
          else np.full(arr.shape[:-1], -1, np.int32))
    return {"survived": arr[..., 0] != 0, "overflow": arr[..., 1] != 0,
            "dead_step": arr[..., 2], "max_frontier": arr[..., 3],
            "configs_explored": arr[..., 4], "live_tile_pm": pm}


_CACHE: dict[tuple, Any] = {}


def cached_batch_checker3(model: Model, cfg: DenseConfig):
    key = ("batch3", model.cache_key(), cfg)
    if key not in _CACHE:
        _CACHE[key] = instrument_kernel("wgl3-batch",
                                        make_batch_checker3(model, cfg))
    return _CACHE[key]


def cached_checker3_packed(model: Model, cfg: DenseConfig):
    key = ("single3p", model.cache_key(), cfg)
    if key not in _CACHE:
        fn = _check_one_fn(model, cfg)
        # jtflow: packed wgl3.PACKED_FIELDS_XLA
        _CACHE[key] = instrument_kernel(
            "wgl3-single", jax.jit(lambda *a: _pack_result(fn(*a))))
    return _CACHE[key]


def cached_batch_checker3_packed(model: Model, cfg: DenseConfig):
    key = ("batch3p", model.cache_key(), cfg)
    if key not in _CACHE:
        fn = jax.vmap(_check_one_fn(model, cfg))
        # jtflow: packed wgl3.PACKED_FIELDS_XLA
        _CACHE[key] = instrument_kernel(
            "wgl3-batch", jax.jit(lambda *a: _pack_result(fn(*a))))
    return _CACHE[key]


def tight_k_for_pending(max_pending: int) -> int:
    """Smallest mask width serving this max_pending, rounded up to even
    so nearby concurrencies share one jit cache entry; floor 6 because
    the packed table needs K >= 5 (and 2^6 masks = 2 words is already
    tiny). The ONE definition of the tight geometry — the streaming
    engine (stream/engine.py) keys on it over a running max_pending, so
    any retune here keeps streamed and post-hoc geometries identical."""
    return max(6, (max_pending + 1) // 2 * 2)


def tight_k_slots(enc: EncodedHistory) -> int:
    """tight_k_for_pending over an encoded history."""
    return tight_k_for_pending(enc.max_pending)


def step_bucket(n_steps: int, floor: int | None = None) -> int:
    """Pad scan lengths to {2^k, 1.5*2^k} buckets: bounded recompiles
    across a corpus of varying history lengths, ≤33% padded steps (pads are
    cheap — the closure while_loop exits immediately on a pad step — but
    the scan still walks them). The default floor is the tunable
    limits().step_bucket_floor — the same boundary set the corpus
    scheduler (sched/engine.py) groups launches by."""
    if floor is None:
        floor = limits().step_bucket_floor
    r = floor
    while r < n_steps:
        if r + r // 2 >= n_steps:
            return r + r // 2
        r *= 2
    return r


def check_steps3(rs: ReturnSteps, model: Model | None = None,
                 cfg: DenseConfig | None = None) -> dict:
    """Single-history entry point over the return-major encoding.

    Low-level: uses rs.k_slots as the mask width verbatim. Callers with an
    EncodedHistory should prefer check_encoded3, which first tightens the
    slot table to the history's real concurrency (a default 32-wide encoding
    would always be rejected here)."""
    from .wgl import verdict

    if model is None:
        from ..models import CASRegister
        model = CASRegister()
    if cfg is None:
        cfg = dense_config(model, rs.k_slots, rs.max_value)
    if cfg is None:
        raise ValueError(
            f"dense kernel infeasible for k_slots={rs.k_slots}, "
            f"max_value={rs.max_value}; use the sort kernel (wgl2)")
    check = cached_checker3_packed(model, cfg)
    out = unpack_np(check(jnp.asarray(rs.slot_tabs),
                          jnp.asarray(rs.slot_active),
                          jnp.asarray(rs.targets)))
    out["valid"] = verdict(out)
    out["configs_explored"] = int(out["configs_explored"])
    out["max_frontier"] = int(out["max_frontier"])
    attach_live_ratio(out)
    record_check_result(out)
    return out


def prepare_dense(enc: EncodedHistory, model: Model,
                  cfg: DenseConfig | None = None
                  ) -> tuple[DenseConfig, ReturnSteps]:
    """Host-side single-history prep shared by check_encoded3 and the
    driver entry (__graft_entry__): tighten the slot table to the
    history's real concurrency, decide dense feasibility, and bucket the
    scan length. `cfg` (when the caller already computed the feasibility
    decision) must come from dense_config(model, tight_k_slots(enc),
    enc.max_value)."""
    from .encode import reslot_events

    k = tight_k_slots(enc)
    if cfg is None:
        cfg = dense_config(model, k, enc.max_value)
    if cfg is None:
        raise ValueError(
            f"dense kernel infeasible: max_pending={enc.max_pending}, "
            f"max_value={enc.max_value}; use the sort kernel (wgl2)")
    if enc.k_slots != k:
        enc = reslot_events(enc, k)
    rs = encode_return_steps(enc)
    padded = rs.padded_to(step_bucket(rs.n_steps))
    _record_padding([rs], padded.slot_tabs.shape[0])
    return cfg, padded


def check_encoded3(enc: EncodedHistory, model: Model | None = None,
                   cfg: DenseConfig | None = None) -> dict:
    """Tighten the slot table to the history's real concurrency, bucket the
    scan length, and run the dense kernel."""
    if model is None:
        from ..models import CASRegister
        model = CASRegister()
    cfg, rs = prepare_dense(enc, model, cfg)
    return check_steps3(rs, model, cfg)


def batch_steps3(encs: Sequence[EncodedHistory], model: Model,
                 cfg: DenseConfig | None = None):
    """HOST-side half of the batched-launch plumbing: tighten/reslot/
    encode a batch into per-history ReturnSteps and the bucketed common
    step count. No device transfer happens here, so routers can inspect
    (cfg, r_cap) and choose a backend before committing tens of MB to
    the device."""
    from .encode import reslot_events

    k = max(tight_k_slots(e) for e in encs)
    if cfg is None:
        cfg = dense_config(model, k, max(e.max_value for e in encs))
    if cfg is None:
        raise ValueError("dense kernel infeasible for this batch")
    steps = [encode_return_steps(
        reslot_events(e, k) if e.k_slots != k else e) for e in encs]
    r_cap = step_bucket(max(s.n_steps for s in steps))
    return cfg, steps, r_cap


def _record_padding(steps, r_cap: int) -> None:
    """Telemetry (obs/): per-launch step-bucket padding waste. Pads are
    cheap (the closure exits immediately; the fused kernel never even
    executes them) but the scan still walks them in the XLA path — the
    gauges make the waste visible per launch instead of folklore. Two
    views per launch: the padded percentage (step_padding_pct) and the
    padded/real RATIO (step_padding_ratio — the number the scheduler's
    <2x bucket-waste bound is stated in), plus running real/padded step
    counters so consumers can aggregate an exact corpus-wide ratio
    instead of averaging per-launch gauges."""
    real = int(sum(s.n_steps for s in steps))
    total = len(steps) * int(r_cap)
    if total:
        m = get_metrics()
        m.gauge("wgl.step_padding_pct").set(100.0 * (1.0 - real / total))
        m.counter("wgl.steps_real").add(real)
        m.counter("wgl.steps_padded").add(total)
        if real:
            m.gauge("wgl.step_padding_ratio").set(total / real)


def stack_steps3(steps, r_cap: int):
    """DEVICE-side half: pad to the common step count, stack, transfer."""
    padded = [s.padded_to(r_cap) for s in steps]
    tabs = np.stack([p.slot_tabs for p in padded])
    act = np.stack([p.slot_active for p in padded])
    tgt = np.stack([p.targets for p in padded])
    _record_padding(steps, r_cap)
    nbytes = int(tabs.nbytes + act.nbytes + tgt.nbytes)
    get_metrics().counter("wgl.h2d_bytes").add(nbytes)
    # Scaling ledger: the host->device staging enqueue wall + bytes (a
    # lower bound on transfer time — async backends overlap the copy).
    t0_ns = time.monotonic_ns()
    out = jnp.asarray(tabs), jnp.asarray(act), jnp.asarray(tgt)
    get_ledger().record_h2d(nbytes, t0_ns, time.monotonic_ns())
    return out


def batch_arrays3(encs: Sequence[EncodedHistory], model: Model,
                  cfg: DenseConfig | None = None):
    """Tighten/reslot/encode/pad/stack a batch of event encodings for one
    vmapped dense launch. Returns (cfg, (tabs, act, tgt), steps) — `steps`
    are the per-history ReturnSteps (for op counts etc)."""
    cfg, steps, r_cap = batch_steps3(encs, model, cfg)
    return cfg, stack_steps3(steps, r_cap), steps


def attach_live_ratio(out: dict) -> None:
    """Fold the packed live_tile_pm telemetry column into the friendly
    live_tile_ratio float (dropped when the launch didn't measure it —
    pallas emits -1)."""
    pm = out.pop("live_tile_pm", -1)
    try:
        pm = int(pm)
    except (TypeError, ValueError):
        pm = -1
    if pm >= 0:
        out["live_tile_ratio"] = min(pm / 1000.0, 1.0)


def assemble_batch_results(out: dict, steps, cfg: DenseConfig) -> list[dict]:
    """Unpacked [B]-array results -> one result dict per history
    (v2-compatible schema + valid). Shared by the XLA and pallas batch
    entry points so the two backends cannot drift apart in schema."""
    from .wgl import verdict

    results = []
    for i, s in enumerate(steps):
        one = {k: out[k][i].item() for k in out}
        one["valid"] = verdict(one)
        one["op_count"] = s.n_ops
        one["configs_explored"] = int(one["configs_explored"])
        one["table_cells"] = cfg.n_states * cfg.n_masks
        attach_live_ratio(one)
        record_check_result(one)
        results.append(one)
    return results


def check_batch_encoded3(encs: Sequence[EncodedHistory],
                         model: Model | None = None) -> list[dict]:
    """Check a batch of histories in one vmapped dense launch; returns one
    result dict per history (v2-compatible schema + valid)."""
    if model is None:
        from ..models import CASRegister
        model = CASRegister()
    cfg, arrays, steps = batch_arrays3(encs, model)
    check = cached_batch_checker3_packed(model, cfg)
    return assemble_batch_results(unpack_np(check(*arrays)), steps, cfg)
