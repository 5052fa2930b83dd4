"""Pallas TPU kernel for the dense subset-lattice WGL search (wgl3).

Same search as ops/wgl3.py (knossos :linear semantics, reference call site
src/jepsen/etcdemo.clj:117 [dep]), fused into ONE kernel per history batch:
the whole return-step scan runs inside the kernel with the reachability
table held on-chip, instead of an XLA `lax.scan` whose per-step closure
round-trips the batched table through HBM.

Why a hand kernel wins here (and what it does differently from wgl3's
XLA formulation):
  * The table u32[S, W] for typical geometry (S=8 states, K=12 slots ⇒
    W=2^7=128 words) is EXACTLY one (8,128) VPU tile. The kernel carries
    it as a loop value — zero HBM traffic between steps; XLA's scan over
    a [B, S, W] batch streams ~1 MiB of table (plus closure temporaries)
    per step.
  * The per-history closure `while_loop` converges independently per
    program. Under `vmap`, XLA lock-steps the loop across the whole batch
    (every history pays the slowest history's round count per step).
  * The mask-bit exposure for slot j >= 5, a [S, hi, 2, lo] reshape in
    XLA (a lane shuffle), becomes a static lane ROLL by 2^(j-5): firing
    slot j moves a config from word w (bit j-5 clear) to word w + 2^(j-5)
    (pltpu.roll + iota mask — the VPU-native formulation).
  * Transition matrices are pre-bitpacked host-side to column masks
    colmask[r, s', j] = bitmask over SOURCE states s (S <= 32 fits u32),
    so the state OR-reduce is S broadcast-selects per slot with no
    scalar loads: sel[s'] = (colmask[:, j] >> s) & 1, a [S,1]x[1,W]
    broadcast against table row s.

Layout contract (prepare_pallas_batch):
  colmask  u32[B, R, Sp, 128]   Sp = S padded to 8 sublanes; lane axis is
                                the slot j (K <= 128); one (8,128) tile
                                per return step.
  targets  i32[B, R]            target slot per return step, -1 = pad.

The kernel is exact (dense table = whole config space, no overflow), so
results match wgl3 bit-for-bit; tests run it in interpreter mode on CPU
against the XLA kernel and the oracle (tests/test_wgl3_pallas.py).

Tuning notes (measured on TPU v5e, 1024x150-op corpus, k=12/S=8, rounds
1-5 through a remote dispatch path no longer used; device-busy times from
jax.profiler, not wall). Round 4 took the grouped corpus launch from 110
ms to 45 ms of device time:
  * The data-dependent fixpoint `while_loop` was ~60% of device time
    (~4 us per loop entry/exit). Each step now runs a PAIR of sweeps
    unconditionally, loops on pairs only while the pair's 2nd sweep grew,
    and takes the metrics popcount ONCE after convergence. Bit-identical:
    extra sweeps past the fixpoint are idempotent.
  * Padded step tails were ~40% of all steps: the launch prefetches
    per-history step counts and bounds the scan trip by the group max —
    pad steps never execute.
  * Broadcasting the colmask column ONCE per slot to full [*,Sp,W] width
    and selecting with arithmetic masks dropped 61 -> 45 ms.
  * Dead ends: tree-OR of the s-loop partials (no change); packing 4
    targets per SMEM word (no change); 2-sweep speculation with host
    escalation (every history needs a 2nd pair); G=32/64 groups
    (scoped-VMEM OOM); Sp=32 grouping (G=2 +6%, G=4 OOM); dynamic
    shift+roll+select prune in the per-history kernel (12% slower than a
    switch — the switch itself is now a cond tree, _prune_tree, because
    a flat K-way switch crashes the compiler at K >= 12).
  * Calibration: an independent 8-chain int32 ALU loop sustains ~4.0 G
    vreg-ops/s (~4.1 T word-ops/s) on a v5e core, against the 6.1 T
    spec-sheet figure (benchmark/peaks.json); one serial
    dependent chain sustains ~0.7 G vreg-ops/s, so ILP shape (not op
    count) dominates kernel cost here.
  * Chip limits (v5e compiles, jax 0.9): SMEM is 1 MiB per core and a
    2-D SMEM table's minor dim pads to 128 words, so a launch's
    scalar-prefetched targets are gated by smem_words and split by
    launch_parts; one group's tables must stay within GROUP_VMEM_WORDS.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.base import Model
from ..obs import instrument_kernel, phase, record_check_result
from .encode import EncodedHistory
from .limits import limits
from .wgl3 import DenseConfig, _LO_MASK, batch_arrays3, dense_config


def prepare_pallas_batch(model: Model, cfg: DenseConfig, slot_tabs, slot_active,
                         targets):
    """Host/XLA-side prep: transition matrices -> bit-packed column masks.

    slot_tabs [B,R,K,4] i32, slot_active [B,R,K] bool, targets [B,R] i32
    (the batched return-major arrays of wgl3.batch_arrays3).
    Returns (colmask u32[B,R,Sp,128], targets i32[B,R], lengths i32[B]):
    `lengths` counts each history's real (non-pad) return steps so the
    kernel can bound its scan trip and skip the padded tail entirely
    (pad targets are -1 and always a suffix — wgl3.stack_steps3).
    """
    K, S, off = cfg.k_slots, cfg.n_states, cfg.state_offset
    state_vals = jnp.arange(S, dtype=jnp.int32) - off
    s_ids = jnp.arange(S, dtype=jnp.int32)

    def trans_one(row, active):
        legal, nxt = model.step(state_vals, row[0], row[1], row[2], row[3])
        nxt_row = nxt + off
        ok = legal & (nxt_row >= 0) & (nxt_row < S) & active
        return ok[:, None] & (nxt_row[:, None] == s_ids[None, :])  # [S,S']

    def pack(tabs, act):                      # [R,K,4],[R,K] for one history
        tj = jax.vmap(jax.vmap(trans_one))(tabs, act)      # [R,K,S,S'] bool
        bits = (tj.astype(jnp.uint32)
                << jnp.arange(S, dtype=jnp.uint32)[None, None, :, None])
        colmask = jnp.sum(bits, axis=2, dtype=jnp.uint32)  # [R,K,S'] over s
        colmask = jnp.swapaxes(colmask, 1, 2)              # [R,S',K]
        sp = max(8, (S + 7) // 8 * 8)
        return jnp.pad(colmask, ((0, 0), (0, sp - S), (0, 128 - K)))

    colmask = jax.vmap(pack)(slot_tabs, slot_active)
    tg = targets.astype(jnp.int32)
    lengths = jnp.sum((tg >= 0).astype(jnp.int32), axis=1)
    return colmask, tg, lengths


def _prune_tree(T, t, allowed, K: int, W: int):
    """Keep the configs whose slot-t bit is set, shifted to clear it:
    one branch per slot, picked by a balanced tree of conds (depth
    log2 K). A flat K-way lax.switch lowers to K-deep nested ifs, whose
    layout inference overflows the TPU compiler's stack at K >= 12
    (v5e compile, jax 0.9). t >= K-1 takes the last branch, as switch's
    clamp did."""
    def variant(j):
        if j < 5:
            return (T >> jnp.uint32(1 << j)) & allowed
        return pltpu.roll(T, W - (1 << (j - 5)), axis=1) & allowed

    def tree(lo: int, hi: int):
        if hi - lo == 1:
            return variant(lo)
        mid = (lo + hi) // 2
        return jax.lax.cond(t < mid, lambda: tree(lo, mid),
                            lambda: tree(mid, hi))

    return tree(0, K)


def _kernel_body(cfg: DenseConfig, resume: bool = False):
    """Per-history kernel. With resume=True the search state enters and
    leaves through operands — extra prefetch `mt` i32[B,5] (dead,
    dead_step, maxf, cfgs, global step offset), extra input T_in and
    extra output T_out — so a host loop (check_steps3_long_pallas) can
    chain launches over step windows: the SMEM prefetch ceiling
    (limits().max_r_pallas) bounds one LAUNCH, not the history. Per-step
    semantics identical either way."""
    K, S, off = cfg.k_slots, cfg.n_states, cfg.state_offset
    W = 1 << (K - 5)
    Sp = max(8, (S + 7) // 8 * 8)
    init_row = None  # bound in closure below

    # NB: every jnp array used by the kernel is constructed INSIDE `body`
    # (pallas kernels may not capture traced constants from build time;
    # Python ints become literals, which is fine).

    def _lane():
        return jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)

    def allowed_mask(t):
        """u32[1, W]: positions whose config mask has bit t CLEAR."""
        full = jnp.uint32(0xFFFFFFFF)
        inword = jnp.uint32(_LO_MASK[4])
        for b in range(3, -1, -1):
            inword = jnp.where(t == b, jnp.uint32(_LO_MASK[b]), inword)
        word_ok = ((_lane() >> jnp.maximum(t - 5, 0)) & 1) == 0
        return jnp.where(t < 5, jnp.broadcast_to(inword, (1, W)),
                         jnp.where(word_ok, full, jnp.uint32(0)))

    def closure(T, cm, allowed):
        """One Gauss-Seidel sweep over all K slots (static unroll).

        The colmask column is broadcast to full [Sp, W] width ONCE per
        slot and the source-state select is an arithmetic mask
        (0 - bit), not a [Sp,1]-shaped where: the narrow-shape variant
        was broadcast-bound (r4 tuning notes)."""
        for j in range(K):
            src = T & allowed                                # [Sp, W]
            colb = jnp.broadcast_to(cm[:, j:j + 1], (Sp, W))  # u32[Sp, W]
            fired = jnp.zeros_like(T)
            for s in range(S):
                selm = (jnp.uint32(0)
                        - ((colb >> jnp.uint32(s)) & jnp.uint32(1)))
                fired = fired | (selm & src[s:s + 1, :])
            if j < 5:
                T = T | ((fired & jnp.uint32(_LO_MASK[j]))
                         << jnp.uint32(1 << j))
            else:
                d = 1 << (j - 5)
                tgt = ((_lane() >> (j - 5)) & 1) == 1        # bit-set lanes
                T = T | jnp.where(tgt, pltpu.roll(fired, d, axis=1),
                                  jnp.uint32(0))
        return T

    def prune(T, t, allowed):
        return _prune_tree(T, t, allowed, K, W)

    # Paired-sweep fixpoint: pairs may overshoot cfg.rounds by one sweep,
    # which is sound because extra sweeps past the fixpoint are
    # idempotent and _require_converging_cap guarantees the cap is never
    # a truncating one (r4 tuning notes — the per-step while_loop entry
    # was ~4 us, so a pair per loop trip halves the scalar conds and
    # drops the per-sweep popcounts entirely).
    MAX_PAIRS = (cfg.rounds + 1) // 2

    def body(ln_ref, *rest):
        """Grid is (B, NC): history b, step-chunk c. The colmask block is
        one RC-step chunk (long histories would blow the 16 MiB VMEM limit
        as a single block); the search state (table + metadata) carries
        across chunks in scratch, which persists over the sequential TPU
        grid. The scan trip is bounded by the history's REAL step count
        (ln_ref scalar prefetch): bucket-pad steps never execute."""
        if resume:
            (mt_ref, tg_ref, cm_ref, Tin_ref, out_ref, Tout_ref, T_s,
             meta_s) = rest
        else:
            mt_ref = Tin_ref = Tout_ref = None
            tg_ref, cm_ref, out_ref, T_s, meta_s = rest
        b = pl.program_id(0)
        c = pl.program_id(1)
        NC = pl.num_programs(1)
        RC = cm_ref.shape[1]

        @pl.when(c == 0)
        def _init():
            if resume:
                # Continue the previous window's search state.
                T_s[:, :] = Tin_ref[0]
                meta_s[0] = mt_ref[b, 0]    # dead
                meta_s[1] = mt_ref[b, 1]    # dead_step (global)
                meta_s[2] = mt_ref[b, 2]    # max_frontier
                meta_s[3] = mt_ref[b, 3]    # configs_explored
            else:
                # Initial table: bit 0 of word 0 in the init-state row
                # (built with iota masks — scatter has no Mosaic
                # lowering).
                rows = jax.lax.broadcasted_iota(jnp.int32, (Sp, W), 0)
                cols = jax.lax.broadcasted_iota(jnp.int32, (Sp, W), 1)
                T_s[:, :] = jnp.where((rows == init_row) & (cols == 0),
                                      jnp.uint32(1), jnp.uint32(0))
                meta_s[0] = 0    # dead
                meta_s[1] = -1   # dead_step
                meta_s[2] = 1    # max_frontier
                meta_s[3] = 0    # configs_explored

        trip = jnp.clip(ln_ref[b] - c * RC, 0, RC)
        # Global step offset: dead_step stays comparable across windows.
        off0 = mt_ref[b, 4] if resume else 0

        def step(i, carry):
            T, dead, dead_step, maxf, cfgs = carry
            r = off0 + c * RC + i
            # trip excludes pads (-1)
            t = jnp.maximum(tg_ref[b, c * RC + i], 0)
            allowed = allowed_mask(t)
            cm = cm_ref[0, i]                                # u32[Sp, 128]

            # One sweep, then PAIRS of sweeps while the last sweep still
            # grew (vector compare; fixpoint detection unchanged, so the
            # result is bit-identical — extra sweeps past the fixpoint
            # are idempotent, and the metrics popcount of a converged
            # table equals the one the per-sweep loop exited with).
            # Single-history steps are often already saturated (first
            # sweep silent): those pay exactly the old 1 sweep + 1 cond,
            # while multi-sweep steps pay roughly half the old scalar
            # conds.
            T1 = closure(T, cm, allowed)

            def wbody(st):
                Tw, _ch, pairs = st
                Ta = closure(Tw, cm, allowed)
                Tb = closure(Ta, cm, allowed)
                return Tb, jnp.any(Ta != Tb), pairs + 1

            def wcond(st):
                return st[1] & (st[2] < MAX_PAIRS)

            T, _ch, _p = jax.lax.while_loop(
                wcond, wbody, (T1, jnp.any(T1 != T), jnp.int32(0)))
            n = jnp.sum(jax.lax.population_count(T), dtype=jnp.int32)

            pruned = prune(T, t, allowed)
            alive = jnp.any(pruned != 0)
            died = ~dead & ~alive
            dead = dead | died
            T_new = jnp.where(dead, jnp.zeros_like(pruned), pruned)
            return (T_new, dead,
                    jnp.where(died & (dead_step < 0), r, dead_step),
                    jnp.maximum(maxf, n),
                    cfgs + n)

        # cfgs accumulates as i32 (a scalar f32 bitcast has no Mosaic
        # lowering); exact up to 2^31 summed configs, beyond which the f32
        # accumulator of the XLA kernel is approximate anyway.
        init = (T_s[:, :], meta_s[0] != 0, meta_s[1], meta_s[2], meta_s[3])
        T, dead, dead_step, maxf, cfgs = jax.lax.fori_loop(0, trip, step,
                                                           init)
        T_s[:, :] = T
        meta_s[0] = dead.astype(jnp.int32)
        meta_s[1] = dead_step
        meta_s[2] = maxf
        meta_s[3] = cfgs

        # ONE flat whole-[5B] 1-D SMEM output block, each program writing
        # its 5 slots (the wgl3 PACKED_FIELDS layout, so the host unpacks
        # both kernels' results identically). Shape matters enormously
        # here: separate [B] output blocks (or one 2-D [B,5] block) cost
        # ~0.33 s/launch at B=256 in per-program block flushes — 3x the
        # whole search — and the TPU lowering rejects 1-element blocks
        # outright, so per-program blocks are not an option either.
        @pl.when(c == NC - 1)
        def _emit():
            # jtflow: packed-width=5 wgl3.PACKED_FIELDS
            out_ref[5 * b + 0] = jnp.where(dead, 0, 1).astype(jnp.int32)
            out_ref[5 * b + 1] = jnp.int32(0)  # overflow: impossible (dense)
            out_ref[5 * b + 2] = dead_step
            out_ref[5 * b + 3] = maxf
            out_ref[5 * b + 4] = cfgs
            if resume:
                Tout_ref[0] = T_s[:, :]

    def bind(row):
        nonlocal init_row
        init_row = row
        return body

    return bind


def local_pallas_launcher_resumable(model: Model, cfg: DenseConfig,
                                    interpret: bool = False):
    """launch(R) for the RESUMABLE per-history kernel (B=1 windows):
    jitted (ln i32[1], mt i32[1,5], tg i32[1,R], cm u32[1,R,Sp,128],
    Tin u32[1,Sp,W], end i32) -> (out i32[5], Tout u32[1,Sp,W],
    mt_next i32[1,5]). The host loop in check_steps3_long_pallas chains
    windows, feeding (Tout, mt_next) straight into the next launch — the
    whole chain is device-side and ONE compiled program per geometry."""
    max_k = limits().max_k_pallas
    if cfg.k_slots > max_k:
        raise ValueError(f"pallas kernel supports k_slots <= {max_k}, "
                         f"got {cfg.k_slots}")
    _require_converging_cap(cfg)
    Sp = max(8, (cfg.n_states + 7) // 8 * 8)
    W = 1 << (cfg.k_slots - 5)
    row = int(model.init_state()) + cfg.state_offset
    kernel = _kernel_body(cfg, resume=True)(row)

    import functools

    @functools.lru_cache(maxsize=None)
    def launch(R: int):
        RC = min(R, limits().pallas_step_chunk)
        NC = (R + RC - 1) // RC
        R_pad = NC * RC
        grid_spec = pltpu.PrefetchScalarGridSpec(
            # lengths [1] + meta [1,5] + targets [1,R_pad], all SMEM
            num_scalar_prefetch=3,
            grid=(1, NC),
            in_specs=[
                pl.BlockSpec((1, RC, Sp, 128),
                             lambda b, c, ln, mt, tg: (b, c, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, Sp, W),
                             lambda b, c, ln, mt, tg: (b, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((5,), lambda b, c, ln, mt, tg: (0,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, Sp, W),
                             lambda b, c, ln, mt, tg: (b, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            scratch_shapes=[
                pltpu.VMEM((Sp, W), jnp.uint32),   # table carry
                pltpu.SMEM((4,), jnp.int32),        # dead/step/maxf/cfgs
            ],
        )

        def run(ln, mt, tg, cm, Tin, end):
            if R_pad != R:
                tg = jnp.pad(tg, ((0, 0), (0, R_pad - R)),
                             constant_values=-1)
                cm = jnp.pad(cm, ((0, 0), (0, R_pad - R), (0, 0), (0, 0)))
            out, Tout = pl.pallas_call(
                kernel,
                grid_spec=grid_spec,
                out_shape=[jax.ShapeDtypeStruct((5,), jnp.int32),
                           jax.ShapeDtypeStruct((1, Sp, W), jnp.uint32)],
                interpret=interpret, name="wgl3_resumable",
            )(ln, mt, tg, cm, Tin)
            # The NEXT window's metadata, chained device-side INSIDE the
            # jit. `end` (the global step offset after this window) is an
            # OPERAND, not a Python int: embedding it as a constant gave
            # every window its own one-off XLA program, and those tiny
            # compiles dwarfed the kernel compile itself (r4: 16.6 s
            # cold, 5 windows of constant-baked stack() programs).
            mt_next = jnp.stack([1 - out[0], out[2], out[3], out[4],
                                 end])[None]
            return out, Tout, mt_next

        # obs/ compile/execute attribution: lru_cache gives one wrapper
        # (and so one first-call flag) per compiled window shape R.
        # mt/Tin are DONATED: the host loop threads them linearly
        # (window N's outputs are window N+1's inputs and nothing else
        # reads the old buffers), so the table aliases in place across
        # the whole chain.
        return instrument_kernel("wgl3-pallas-resumable",
                                 jax.jit(run, donate_argnums=(1, 4)))

    return launch


def check_steps3_long_pallas(rs, model: Model, cfg: DenseConfig,
                             time_budget_s: float | None = None,
                             interpret: bool = False) -> dict:
    """Host-chained fused-kernel sweep for single histories whose step
    count exceeds one launch's SMEM prefetch budget (the 100k-op lane):
    windows of limits().max_r_pallas steps, the search state (table +
    metadata + global step offset) carried between launches as operands.
    Same verdict/metrics contract as wgl3.check_steps3_long, with the
    kernel-side i32 configs accumulator (exact where the XLA path's f32
    partial sums are approximate past 2^24).

    Geometries the density signal selects sparse for (the SAME
    sparse_plan policy the XLA engine routes by — prefer-sparse
    sparse_mode=2 forces it, auto mode engages past the measured
    crossover) take the sparse work-list kernel instead
    (check_steps3_long_pallas_sparse) — bit-identical verdicts, plus
    the sweep telemetry record. This is the routed DEFAULT since
    ISSUE 10; sparse_mode=1 keeps the dense kernel unconditionally."""
    import time as _time

    from . import wgl3
    from .wgl import verdict

    lim = limits()
    if pallas_sparse_selected(cfg):
        return check_steps3_long_pallas_sparse(
            rs, model, cfg, time_budget_s=time_budget_s,
            interpret=interpret)
    t0 = _time.monotonic()
    # Window pads never execute — the kernel bounds its trip with the
    # prefetched length.
    window = _long_window(lim)
    launch = _cached_resumable_launcher(model, cfg, interpret)
    prep = _cached_prep(model, cfg)
    Sp = max(8, (cfg.n_states + 7) // 8 * 8)
    W = 1 << (cfg.k_slots - 5)
    Tin = np.zeros((1, Sp, W), np.uint32)
    Tin[0, int(model.init_state()) + cfg.state_offset, 0] = 1
    Tin = jnp.asarray(Tin)
    meta = jnp.asarray(np.array([[0, -1, 1, 0, 0]], np.int32))
    n = rs.n_steps
    if n == 0:
        # The initial state trivially survives an empty history (same
        # contract as the XLA path finalizing its init carry).
        return {"survived": True, "overflow": False, "dead_step": -1,
                "max_frontier": 1, "configs_explored": 0, "valid": True}
    out = None
    # Unbudgeted: all windows dispatch ASYNC, metadata chained
    # device-side, ONE fetch at the end (a dead table makes the
    # remaining windows near-free — empty closures — so no early-exit
    # fetch: a fetch per window would cost more than the sweep).
    # Budgeted: sync per window so the budget check sees device time —
    # overshoot bounded by one window, same contract as the XLA rung.
    for w0 in range(0, n, window):
        if (time_budget_s is not None
                and _time.monotonic() - t0 > time_budget_s):
            return {"valid": "unknown", "survived": False, "overflow": True,
                    "dead_step": -1, "max_frontier": -1,
                    "configs_explored": -1, "kernel": "exhausted",
                    "error": f"pallas long sweep exceeded its "
                             f"{time_budget_s:.0f}s time budget at return "
                             f"step {w0}"}
        wn = min(window, n - w0)
        sl = slice(w0, w0 + wn)
        pad = ((0, window - wn),)
        with phase("sched.pack"):
            tg = np.pad(rs.targets[sl], pad, constant_values=-1)[None]
            tabs = np.pad(rs.slot_tabs[sl],
                          pad + ((0, 0), (0, 0)))[None]
            act = np.pad(rs.slot_active[sl], pad + ((0, 0),))[None]
        with phase("sched.dispatch"):
            cm, tgd, ln = prep(jnp.asarray(tabs), jnp.asarray(act),
                               jnp.asarray(tg))
            out, Tin, meta = launch(window)(
                ln, meta, tgd, cm, Tin, jnp.asarray(w0 + wn, jnp.int32))
        if time_budget_s is not None:
            with phase("sched.fetch_wait"):
                np.asarray(out)   # sync: bound overshoot by one window
    with phase("sched.fetch_wait"):
        out_np = np.asarray(out)
    cfgs = int(out_np[4])
    if cfgs < 0:
        # The i32 accumulator wrapped across windows: saturate, matching
        # the XLA path's clip of its (equally approximate past 2^24) f32
        # partial sums. A wrapped-back-to-positive count is undetectable
        # here — both paths' counters are documented approximate at this
        # scale; verdict fields are unaffected.
        cfgs = 2**31 - 1
    res = {
        "survived": bool(out_np[0]),
        "overflow": False,
        "dead_step": int(out_np[2]),
        "max_frontier": int(out_np[3]),
        "configs_explored": cfgs,
    }
    res["valid"] = verdict(res)
    record_check_result(res)
    return res


def _long_window(lim) -> int:
    """Window length of the host-chained resumable sweeps: the largest
    step BUCKET that fits one launch's SMEM prefetch ceiling
    (lim.max_r_pallas), so every window reuses ONE compiled shape; a
    sub-64 cap skips bucketing entirely. One copy shared by the dense
    and sparse long sweeps so they window — and cache compiled window
    shapes — identically."""
    from . import wgl3

    window = lim.max_r_pallas
    if window >= 64:
        b = 64
        while wgl3.step_bucket(b + 1) <= lim.max_r_pallas:
            b = wgl3.step_bucket(b + 1)
        window = b
    return window


# -- sparse work-list kernel (opt-in: limits().sparse_mode == 2) -----------

SPARSE_BLOCK_LANES = 128   # one VPU lane-tile of packed words per block


def pallas_sparse_blocks(cfg: DenseConfig) -> int:
    """Work-list block count of the sparse pallas kernel for this
    geometry, or 0 when it cannot engage: the table must span at least
    two 128-lane blocks (K >= 13) inside the pallas envelope, and the
    sweep cap must be converging (same constraint as the dense paired
    sweeps). NOTE the envelope means sparsity buys less here than in the
    XLA engine (K <= max_k_pallas caps the table at a handful of lane
    tiles, and per-block scalar control costs ~the block's own vector
    work — the r4 tuning notes' overhead analysis); the kernel is
    therefore OPT-IN via sparse_mode=2, and K > max_k_pallas geometries
    take the XLA/lattice sparse engine, which is where the 2^K waste
    actually lives."""
    if cfg.k_slots > limits().max_k_pallas:
        return 0
    if cfg.max_rounds and cfg.max_rounds < cfg.k_slots:
        return 0
    w = 1 << (cfg.k_slots - 5)
    nb = w // SPARSE_BLOCK_LANES
    return nb if nb >= 2 else 0


def pallas_sparse_selected(cfg: DenseConfig) -> bool:
    """Routing predicate of the pallas long sweep: take the sparse
    work-list kernel wherever the DENSITY SIGNAL already selects sparse
    for this geometry — literally the XLA engine's own sparse_plan
    policy (sparse_mode 0 engages past the measured sparse_min_tiles
    crossover, 2 forces it, 1 disables) — provided the table spans
    work-list blocks at all. ISSUE 10 flipped this from the old
    explicit sparse_mode=2 opt-in: a tuned profile that lowers the
    crossover (tune/probes.py `sparse` and `pallas` groups) now routes
    the Mosaic work-list kernel by default, no operator pin needed."""
    if not pallas_sparse_blocks(cfg):
        return False
    from .wgl3_sparse import sparse_plan

    return sparse_plan(cfg) is not None


def _kernel_body_sparse_resumable(cfg: DenseConfig, nb: int,
                                  thresh_blocks: int):
    """Resumable per-history kernel with the sparse active-block sweep:
    each closure round builds an SMEM WORK LIST of live 128-lane blocks
    (one pass of per-block any-nonzero scalar probes), then sweeps only
    the listed blocks — in-word and in-block mask bits expand locally
    with Gauss-Seidel chaining, block-index bits read-modify-write the
    destination block of the table carry directly (the fori over the
    work list is sequential, so the RMW is race-free). Rounds whose live
    count crosses `thresh_blocks` run the dense closure instead (the
    direction-optimizing switch; the list always has capacity for all
    `nb` blocks, so overflow cannot occur here). Same fixpoint, same
    metadata contract as _kernel_body(resume=True) widened to 8 slots:
    [dead, dead_step, maxf, cfgs, offset, live_sum, sparse_steps,
    real_steps]."""
    K, S, off = cfg.k_slots, cfg.n_states, cfg.state_offset
    W = 1 << (K - 5)
    Sp = max(8, (S + 7) // 8 * 8)
    BLK = SPARSE_BLOCK_LANES
    bbits = BLK.bit_length() - 1          # 7: lane bits inside a block
    assert nb * BLK == W and nb >= 2
    # No init_row/bind() here: this kernel is resume-only — the table
    # always enters through the Tin operand (the host seeds window 0).

    def _lane():
        return jax.lax.broadcasted_iota(jnp.int32, (1, BLK), 1)

    def _lane_full():
        return jax.lax.broadcasted_iota(jnp.int32, (1, W), 1)

    def allowed_full(t):
        full = jnp.uint32(0xFFFFFFFF)
        inword = jnp.uint32(_LO_MASK[4])
        for b in range(3, -1, -1):
            inword = jnp.where(t == b, jnp.uint32(_LO_MASK[b]), inword)
        word_ok = ((_lane_full() >> jnp.maximum(t - 5, 0)) & 1) == 0
        return jnp.where(t < 5, jnp.broadcast_to(inword, (1, W)),
                         jnp.where(word_ok, full, jnp.uint32(0)))

    def allowed_block(b, t):
        """u32[1, BLK]: the allowed mask restricted to block b (global
        word index = b * BLK + lane)."""
        full = jnp.uint32(0xFFFFFFFF)
        inword = jnp.uint32(_LO_MASK[4])
        for k in range(3, -1, -1):
            inword = jnp.where(t == k, jnp.uint32(_LO_MASK[k]), inword)
        lane_g = b * BLK + _lane()
        word_ok = ((lane_g >> jnp.maximum(t - 5, 0)) & 1) == 0
        return jnp.where(t < 5, jnp.broadcast_to(inword, (1, BLK)),
                         jnp.where(word_ok, full, jnp.uint32(0)))

    def fire_slot(cm, j, src):
        """OR-reduce over source states for slot j, any width: the
        colmask column broadcast + arithmetic-select formulation of the
        dense closure (r4 tuning notes)."""
        colb = jnp.broadcast_to(cm[:, j:j + 1], (Sp, src.shape[-1]))
        fired = jnp.zeros_like(src)
        for s in range(S):
            selm = (jnp.uint32(0)
                    - ((colb >> jnp.uint32(s)) & jnp.uint32(1)))
            fired = fired | (selm & src[s:s + 1, :])
        return fired

    def dense_closure(T, cm, allowed):
        """One full-width Gauss-Seidel sweep — the dense fallback round
        (same algebra as _kernel_body's closure)."""
        for j in range(K):
            src = T & allowed
            fired = fire_slot(cm, j, src)
            if j < 5:
                T = T | ((fired & jnp.uint32(_LO_MASK[j]))
                         << jnp.uint32(1 << j))
            else:
                d = 1 << (j - 5)
                tgt = ((_lane_full() >> (j - 5)) & 1) == 1
                T = T | jnp.where(tgt, pltpu.roll(fired, d, axis=1),
                                  jnp.uint32(0))
        return T

    def body(ln_ref, mt_ref, tg_ref, cm_ref, Tin_ref, out_ref, Tout_ref,
             T_s, meta_s, wl_s):
        b0 = pl.program_id(0)
        c = pl.program_id(1)
        NC = pl.num_programs(1)
        RC = cm_ref.shape[1]

        @pl.when(c == 0)
        def _init():
            T_s[:, :] = Tin_ref[0]
            for i, slot in enumerate((0, 1, 2, 3, 5, 6, 7)):
                meta_s[i] = mt_ref[b0, slot]

        trip = jnp.clip(ln_ref[b0] - c * RC, 0, RC)
        off0 = mt_ref[b0, 4]

        # Blocks are addressed through the table scratch ref: a
        # dynamic lane slice of a VALUE has no Mosaic lowering, a
        # 128-aligned pl.ds on a VMEM ref does. T_s doubles as the
        # working copy inside a step; the carry is written back to it
        # after the step loop.
        def blk_at(bi):
            return pl.ds(pl.multiple_of(bi * BLK, BLK), BLK)

        def count_live(T):
            T_s[:, :] = T

            def probe(bi, cnt):
                blk = T_s[:, blk_at(bi)]
                return cnt + jnp.any(blk != 0).astype(jnp.int32)
            return jax.lax.fori_loop(0, nb, probe, jnp.int32(0))

        def build_worklist():
            def probe(bi, cnt):
                liveb = jnp.any(T_s[:, blk_at(bi)] != 0)

                @pl.when(liveb)
                def _():
                    wl_s[cnt] = bi
                return cnt + liveb.astype(jnp.int32)
            return jax.lax.fori_loop(0, nb, probe, jnp.int32(0))

        def step(i, carry):
            (T, dead, dead_step, maxf, cfgs, live_sum, sp_steps,
             real_steps) = carry
            r = off0 + c * RC + i
            t = jnp.maximum(tg_ref[b0, c * RC + i], 0)
            allowed = allowed_full(t)
            cm = cm_ref[0, i]                                # u32[Sp, 128]

            def sparse_sweep(T):
                def do_blk(wi, carry):
                    bi = wl_s[wi]
                    blk = T_s[:, blk_at(bi)]
                    ab = allowed_block(bi, t)
                    newblk = blk
                    src = blk & ab
                    for j in range(min(K, 5 + bbits)):
                        fired = fire_slot(cm, j, src)
                        if j < 5:
                            newblk = newblk | (
                                (fired & jnp.uint32(_LO_MASK[j]))
                                << jnp.uint32(1 << j))
                        else:
                            d = 1 << (j - 5)
                            tgt = ((_lane() >> (j - 5)) & 1) == 1
                            newblk = newblk | jnp.where(
                                tgt, pltpu.roll(fired, d, axis=1),
                                jnp.uint32(0))
                        src = newblk & ab   # Gauss-Seidel inside the block
                    T_s[:, blk_at(bi)] = newblk
                    for j in range(5 + bbits, K):
                        # Block-index bit: RMW the destination block.
                        bb = j - 5 - bbits
                        fired = fire_slot(cm, j, src)
                        fired = jnp.where(((bi >> bb) & 1) == 0, fired,
                                          jnp.uint32(0))
                        dest = bi | (1 << bb)
                        T_s[:, blk_at(dest)] = T_s[:, blk_at(dest)] | fired
                    return carry
                T_s[:, :] = T
                live = build_worklist()
                jax.lax.fori_loop(0, live, do_blk, jnp.int32(0))
                return T_s[:, :]

            def wbody(st):
                Tw, _ch, rounds, sp_rounds = st
                live = count_live(Tw)
                take = live <= thresh_blocks
                Tn = jax.lax.cond(take, sparse_sweep,
                                  lambda T: dense_closure(T, cm, allowed),
                                  Tw)
                return (Tn, jnp.any(Tn != Tw), rounds + 1,
                        sp_rounds + take.astype(jnp.int32))

            def wcond(st):
                return st[1] & (st[2] < cfg.rounds)

            T, _ch, rounds, sp_rounds = jax.lax.while_loop(
                wcond, wbody, (T, jnp.bool_(True), jnp.int32(0),
                               jnp.int32(0)))
            n = jnp.sum(jax.lax.population_count(T), dtype=jnp.int32)
            live_fin = count_live(T)

            # Prune: full-width, same as the dense kernel.
            pruned = _prune_tree(T, t, allowed, K, W)
            alive = jnp.any(pruned != 0)
            died = ~dead & ~alive
            dead = dead | died
            T_new = jnp.where(dead, jnp.zeros_like(pruned), pruned)
            return (T_new, dead,
                    jnp.where(died & (dead_step < 0), r, dead_step),
                    jnp.maximum(maxf, n), cfgs + n,
                    live_sum + live_fin,
                    sp_steps + (sp_rounds == rounds).astype(jnp.int32),
                    real_steps + 1)

        init = (T_s[:, :], meta_s[0] != 0, meta_s[1], meta_s[2], meta_s[3],
                meta_s[4], meta_s[5], meta_s[6])
        (T, dead, dead_step, maxf, cfgs, live_sum, sp_steps,
         real_steps) = jax.lax.fori_loop(0, trip, step, init)
        T_s[:, :] = T
        meta_s[0] = dead.astype(jnp.int32)
        meta_s[1] = dead_step
        meta_s[2] = maxf
        meta_s[3] = cfgs
        meta_s[4] = live_sum
        meta_s[5] = sp_steps
        meta_s[6] = real_steps

        @pl.when(c == NC - 1)
        def _emit():
            out_ref[0] = jnp.where(dead, 0, 1).astype(jnp.int32)
            out_ref[1] = jnp.int32(0)   # overflow: impossible (dense table)
            out_ref[2] = dead_step
            out_ref[3] = maxf
            out_ref[4] = cfgs
            out_ref[5] = live_sum
            out_ref[6] = sp_steps
            out_ref[7] = real_steps
            Tout_ref[0] = T_s[:, :]

    return body


def local_pallas_launcher_sparse_resumable(model: Model, cfg: DenseConfig,
                                           interpret: bool = False):
    """launch(R) for the SPARSE resumable kernel: jitted (ln i32[1],
    mt i32[1,8], tg i32[1,R], cm u32[1,R,Sp,128], Tin u32[1,Sp,W], end)
    -> (out i32[8], Tout, mt_next i32[1,8]) — the 8-slot twin of
    local_pallas_launcher_resumable, carrying the sweep telemetry
    (live-block sum, sparse-step count, real steps) through the window
    chain alongside the verdict metadata."""
    nb = pallas_sparse_blocks(cfg)
    if not nb:
        raise ValueError(f"sparse pallas kernel infeasible for "
                         f"k_slots={cfg.k_slots}")
    _require_converging_cap(cfg)
    lim = limits()
    thresh = (nb if lim.sparse_mode == 2
              else max(1, nb * lim.sparse_density_threshold_pct // 100))
    Sp = max(8, (cfg.n_states + 7) // 8 * 8)
    W = 1 << (cfg.k_slots - 5)
    kernel = _kernel_body_sparse_resumable(cfg, nb, thresh)

    import functools

    @functools.lru_cache(maxsize=None)
    def launch(R: int):
        RC = min(R, limits().pallas_step_chunk)
        NC = (R + RC - 1) // RC
        R_pad = NC * RC
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1, NC),
            in_specs=[
                pl.BlockSpec((1, RC, Sp, 128),
                             lambda b, c, ln, mt, tg: (b, c, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, Sp, W),
                             lambda b, c, ln, mt, tg: (b, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((8,), lambda b, c, ln, mt, tg: (0,),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, Sp, W),
                             lambda b, c, ln, mt, tg: (b, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            scratch_shapes=[
                pltpu.VMEM((Sp, W), jnp.uint32),   # table carry
                pltpu.SMEM((7,), jnp.int32),        # metadata carry
                pltpu.SMEM((nb,), jnp.int32),       # the block work list
            ],
        )

        def run(ln, mt, tg, cm, Tin, end):
            if R_pad != R:
                tg = jnp.pad(tg, ((0, 0), (0, R_pad - R)),
                             constant_values=-1)
                cm = jnp.pad(cm, ((0, 0), (0, R_pad - R), (0, 0), (0, 0)))
            out, Tout = pl.pallas_call(
                kernel,
                grid_spec=grid_spec,
                out_shape=[jax.ShapeDtypeStruct((8,), jnp.int32),
                           jax.ShapeDtypeStruct((1, Sp, W), jnp.uint32)],
                interpret=interpret, name="wgl3_sparse_resumable",
            )(ln, mt, tg, cm, Tin)
            mt_next = jnp.stack([1 - out[0], out[2], out[3], out[4], end,
                                 out[5], out[6], out[7]])[None]
            return out, Tout, mt_next

        return instrument_kernel("wgl3-pallas-sparse-resumable",
                                 jax.jit(run, donate_argnums=(1, 4)))

    return launch


def _cached_sparse_resumable_launcher(model: Model, cfg: DenseConfig,
                                      interpret: bool = False):
    lim = limits()
    key = ("pallas-sparse-resumable", model.cache_key(), cfg, interpret,
           lim.sparse_mode, lim.sparse_density_threshold_pct)
    if key not in _CACHE:
        _CACHE[key] = local_pallas_launcher_sparse_resumable(
            model, cfg, interpret)
    return _CACHE[key]


def check_steps3_long_pallas_sparse(rs, model: Model, cfg: DenseConfig,
                                    time_budget_s: float | None = None,
                                    interpret: bool = False) -> dict:
    """Host-chained SPARSE fused-kernel sweep: the work-list kernel's
    twin of check_steps3_long_pallas (same windowing, same budget
    contract, bit-identical verdicts), plus the sweep-mode/live-block
    telemetry record. Routed by default wherever the density signal
    selects sparse (pallas_sparse_selected — the ISSUE 10 flip;
    sparse_mode=2 still forces it for measurement)."""
    import time as _time

    from . import wgl3
    from .wgl import verdict

    nb = pallas_sparse_blocks(cfg)
    if not nb:
        raise ValueError(f"sparse pallas kernel infeasible for "
                         f"k_slots={cfg.k_slots}")
    t0 = _time.monotonic()
    lim = limits()
    window = _long_window(lim)
    launch = _cached_sparse_resumable_launcher(model, cfg, interpret)
    prep = _cached_prep(model, cfg)
    Sp = max(8, (cfg.n_states + 7) // 8 * 8)
    W = 1 << (cfg.k_slots - 5)
    Tin = np.zeros((1, Sp, W), np.uint32)
    Tin[0, int(model.init_state()) + cfg.state_offset, 0] = 1
    Tin = jnp.asarray(Tin)
    meta = jnp.asarray(np.array([[0, -1, 1, 0, 0, 0, 0, 0]], np.int32))
    n = rs.n_steps
    if n == 0:
        return {"survived": True, "overflow": False, "dead_step": -1,
                "max_frontier": 1, "configs_explored": 0, "valid": True}
    out = None
    for w0 in range(0, n, window):
        if (time_budget_s is not None
                and _time.monotonic() - t0 > time_budget_s):
            return {"valid": "unknown", "survived": False, "overflow": True,
                    "dead_step": -1, "max_frontier": -1,
                    "configs_explored": -1, "kernel": "exhausted",
                    "error": f"sparse pallas long sweep exceeded its "
                             f"{time_budget_s:.0f}s time budget at return "
                             f"step {w0}"}
        wn = min(window, n - w0)
        sl = slice(w0, w0 + wn)
        pad = ((0, window - wn),)
        with phase("sched.pack"):
            tg = np.pad(rs.targets[sl], pad, constant_values=-1)[None]
            tabs = np.pad(rs.slot_tabs[sl], pad + ((0, 0), (0, 0)))[None]
            act = np.pad(rs.slot_active[sl], pad + ((0, 0),))[None]
        with phase("sched.dispatch"):
            cm, tgd, ln = prep(jnp.asarray(tabs), jnp.asarray(act),
                               jnp.asarray(tg))
            out, Tin, meta = launch(window)(
                ln, meta, tgd, cm, Tin, jnp.asarray(w0 + wn, jnp.int32))
        if time_budget_s is not None:
            with phase("sched.fetch_wait"):
                np.asarray(out)
    with phase("sched.fetch_wait"):
        out_np = np.asarray(out)
    cfgs = int(out_np[4])
    if cfgs < 0:
        cfgs = 2**31 - 1
    res = {
        "survived": bool(out_np[0]),
        "overflow": False,
        "dead_step": int(out_np[2]),
        "max_frontier": int(out_np[3]),
        "configs_explored": cfgs,
        "kernel": "wgl3-dense-pallas-sparse-chunked",
    }
    res["sweep"] = wgl3.sweep_summary(
        cfg, live_sum=float(max(0, int(out_np[5]))),
        real_steps=int(out_np[7]), sparse_steps=int(out_np[6]),
        tiling=(SPARSE_BLOCK_LANES, nb))
    res["live_tile_ratio"] = res["sweep"]["live_tile_ratio"]
    res["valid"] = verdict(res)
    record_check_result(res)
    return res


def _cached_resumable_launcher(model: Model, cfg: DenseConfig,
                               interpret: bool = False):
    key = ("pallas-resumable", model.cache_key(), cfg, interpret)
    if key not in _CACHE:
        _CACHE[key] = local_pallas_launcher_resumable(model, cfg,
                                                      interpret)
    return _CACHE[key]


def _cached_prep(model: Model, cfg: DenseConfig):
    import functools

    key = ("pallas-prep", model.cache_key(), cfg)
    if key not in _CACHE:
        _CACHE[key] = instrument_kernel(
            "wgl3-pallas-prep",
            jax.jit(functools.partial(prepare_pallas_batch, model, cfg)))
    return _CACHE[key]


def _require_converging_cap(cfg: DenseConfig) -> None:
    """The paired-sweep loops assume cfg.rounds never TRUNCATES the
    closure (pairs can overshoot a sub-convergence cap, diverging from
    the XLA kernel's exact per-sweep cut-off). With the default
    max_rounds=0 the cap is k_slots, which provably bounds the fixpoint
    (each firing sets a distinct slot bit), so this only rejects explicit
    sub-convergence caps — no production config sets one."""
    if cfg.max_rounds and cfg.max_rounds < cfg.k_slots:
        raise ValueError(
            f"pallas kernels require a converging sweep cap: "
            f"max_rounds={cfg.max_rounds} < k_slots={cfg.k_slots} would "
            f"truncate the closure; use the XLA kernel for truncated "
            f"sweeps")


def local_pallas_launcher(model: Model, cfg: DenseConfig,
                          interpret: bool = False):
    """The pallas-call half of the checker: launch(B, R) -> jitted
    (ln i32[B], tg i32[B,R], cm u32[B,R,Sp,128]) -> i32[B,5]. Exposed
    separately so the mesh-sharded form (parallel/dense.py) can run it
    under shard_map, each device launching its own (B/D, NC) grid over
    its batch shard. `ln` is the per-history real step count
    (prepare_pallas_batch's third output) bounding the kernel's scan
    trip."""
    max_k = limits().max_k_pallas
    if cfg.k_slots > max_k:
        raise ValueError(f"pallas kernel supports k_slots <= {max_k}, "
                         f"got {cfg.k_slots}")
    _require_converging_cap(cfg)
    Sp = max(8, (cfg.n_states + 7) // 8 * 8)
    W = 1 << (cfg.k_slots - 5)
    row = int(model.init_state()) + cfg.state_offset
    kernel = _kernel_body(cfg)(row)

    import functools

    @functools.lru_cache(maxsize=None)
    def launch(B: int, R: int):
        # Chunk the step axis: one colmask block of RC steps per grid
        # iteration (a whole 10k-step history as a single block would need
        # 32 MiB of VMEM against the 16 MiB limit); search state carries
        # across chunks in scratch.
        RC = _step_chunk(R, 1, Sp)
        NC = (R + RC - 1) // RC
        R_pad = NC * RC

        def part_call(start: int, size: int):
            # lengths [size] + targets [size,R_pad], both whole in SMEM;
            # the colmask stays one array, offset by the part's start.
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(size, NC),
                in_specs=[
                    pl.BlockSpec((1, RC, Sp, 128),
                                 lambda b, c, ln_ref, tg_ref:
                                 (b + start, c, 0, 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=[pl.BlockSpec((5 * size,),
                                        lambda b, c, ln_ref, tg_ref: (0,),
                                        memory_space=pltpu.SMEM)],
                scratch_shapes=[
                    pltpu.VMEM((Sp, W), jnp.uint32),   # table carry
                    pltpu.SMEM((4,), jnp.int32),        # dead/step/maxf/cfgs
                ],
            )
            return pl.pallas_call(
                kernel, grid_spec=grid_spec,
                out_shape=[jax.ShapeDtypeStruct((5 * size,), jnp.int32)],
                interpret=interpret, name="wgl3_per_history")

        parts = launch_parts(B, R_pad, 1)

        def run(ln, tg, cm):
            if R_pad != R:
                tg = jnp.pad(tg, ((0, 0), (0, R_pad - R)),
                             constant_values=-1)
                cm = jnp.pad(cm, ((0, 0), (0, R_pad - R), (0, 0), (0, 0)))
            return _run_parts(part_call, parts, ln, tg, cm).reshape(B, 5)

        # jtflow: packed wgl3.PACKED_FIELDS
        return instrument_kernel("wgl3-pallas", jax.jit(run))

    return launch


def cached_pallas_launcher(model: Model, cfg: DenseConfig,
                           interpret: bool = False):
    key = ("pallas-launch", model.cache_key(), cfg, interpret)
    if key not in _CACHE:
        _CACHE[key] = local_pallas_launcher(model, cfg, interpret)
    return _CACHE[key]


def make_batch_checker_pallas(model: Model, cfg: DenseConfig,
                              interpret: bool = False):
    """check(slot_tabs[B,R,K,4], slot_active[B,R,K], targets[B,R]) ->
    DEVICE i32[B, 5] packed results (wgl3.PACKED_FIELDS / unpack_np)."""
    import functools

    # Two SEPARATE jits, sequenced in Python: fusing the transition prep
    # into the same XLA program as the pallas custom-call serializes
    # pathologically on TPU (0.54 s vs 0.12 s for the identical work at
    # B=256); as separate dispatches they pipeline. The prep jit is
    # shared with the resumable long sweep (_cached_prep).
    prep = _cached_prep(model, cfg)
    launch = cached_pallas_launcher(model, cfg, interpret)

    def check(slot_tabs, slot_active, targets):
        """DEVICE i32[B, 5] in the wgl3 PACKED_FIELDS layout — the caller
        fetches once and splits host-side (wgl3.unpack_np). One fetch per
        launch: each fetch is a device->host round trip."""
        colmask, tg, lengths = prep(slot_tabs, slot_active, targets)
        B, R = targets.shape
        return launch(B, R)(lengths, tg, colmask)

    return check


def _kernel_body_grouped(cfg: DenseConfig, G: int):
    """Grouped kernel: G histories per pallas program, tables stacked on a
    leading group axis (u32[G, Sp, W] in VMEM — G tiles of (8,128)).

    Why: the per-history kernel measures ~3-4 us per return step against
    ~0.3 us of actual tile work — per-step instruction overhead (loop
    control, the prune switch, scalar SMEM reads, popcount fixpoint
    checks) dominates on one (8,128) tile. Stacking G histories makes
    every vector instruction carry G tiles, amortizing that overhead ~G
    times; the costs are lockstep convergence (each step runs max rounds
    over the group) and a vectorized data-driven prune (every variant
    computed once per step, selected per history) instead of one switch
    branch. Measured on v5e, 1024x150-op corpus (r4 paired-sweep design):
    48 ms device time vs ~230 ms per-history — grouping plus the r4
    redesign together are ~2.3x over r3's grouped kernel (110 ms) and the
    corpus wall sat at ~0.10 s behind the round-4 remote dispatch path.

    Semantics are identical to _kernel_body per history (same banking,
    same fixpoint sweep order, same metrics; pads contribute nothing)."""
    K, S, off = cfg.k_slots, cfg.n_states, cfg.state_offset
    W = 1 << (K - 5)
    Sp = max(8, (S + 7) // 8 * 8)
    init_row = None

    # Mosaic cannot shape-cast 1-D vectors to higher rank ([G] -> [G,1,1]
    # is an unsupported tpu.reshape), so per-history values are built
    # DIRECTLY in [G,1,1] form: an iota-select chain over the G scalars,
    # and scalars are read back out as masked full-reductions. No 1-D
    # vectors exist anywhere in this kernel.

    def _lane3():
        return jax.lax.broadcasted_iota(jnp.int32, (1, 1, W), 2)

    def _gidx():
        return jax.lax.broadcasted_iota(jnp.int32, (G, 1, 1), 0)

    def g3(scalars, dtype=jnp.int32):
        """[G,1,1] from G scalars (static G, tiny select chain)."""
        acc = jnp.zeros((G, 1, 1), dtype)
        gi = _gidx()
        for g, s in enumerate(scalars):
            acc = jnp.where(gi == g, s.astype(dtype), acc)
        return acc

    def scalar_of(vec3, g):
        """Scalar extraction as a masked full-reduce ([G,1,1] is tiny and
        element extraction from vectors does not lower)."""
        return jnp.sum(jnp.where(_gidx() == g, vec3, 0))

    def allowed_mask(tv3):
        """u32[G, 1, W] from per-history targets tv3 i32[G,1,1]."""
        full = jnp.uint32(0xFFFFFFFF)
        inword = jnp.broadcast_to(jnp.uint32(_LO_MASK[4]), (G, 1, 1))
        for b in range(3, -1, -1):
            inword = jnp.where(tv3 == b, jnp.uint32(_LO_MASK[b]), inword)
        shift = jnp.maximum(tv3 - 5, 0)
        word_ok = ((_lane3() >> shift) & 1) == 0              # [G,1,W]
        word_level = jnp.where(word_ok, full, jnp.uint32(0))
        return jnp.where(tv3 < 5, inword, word_level)

    def closure(T, cm, allowed):
        """One Gauss-Seidel sweep, all G histories: T u32[G,Sp,W],
        cm u32[G,Sp,128], allowed u32[G,1,W]. Column broadcast once per
        slot + arithmetic select masks (r4 tuning notes: the [G,Sp,1]
        where-chain was broadcast-bound, 61 -> 45 ms)."""
        for j in range(K):
            src = T & allowed
            colb = jnp.broadcast_to(cm[:, :, j:j + 1], (G, Sp, W))
            fired = jnp.zeros_like(T)
            for s in range(S):
                selm = (jnp.uint32(0)
                        - ((colb >> jnp.uint32(s)) & jnp.uint32(1)))
                fired = fired | (selm & src[:, s:s + 1, :])
            if j < 5:
                T = T | ((fired & jnp.uint32(_LO_MASK[j]))
                         << jnp.uint32(1 << j))
            else:
                d = 1 << (j - 5)
                tgt = ((_lane3() >> (j - 5)) & 1) == 1
                T = T | jnp.where(tgt, pltpu.roll(fired, d, axis=2),
                                  jnp.uint32(0))
        return T

    def prune(T, tv3, allowed):
        """Data-driven prune: per-history dynamic targets preclude one
        switch branch — compute every slot's variant once (static
        addressing) and select per history. K ~ 12 extra shifted copies
        per STEP, amortized over G histories."""
        acc = jnp.zeros_like(T)
        for j in range(K):
            if j < 5:
                pj = (T >> jnp.uint32(1 << j)) & allowed
            else:
                d = 1 << (j - 5)
                pj = pltpu.roll(T, W - d, axis=2) & allowed
            acc = jnp.where(tv3 == j, pj, acc)
        return acc

    def popcounts(T):
        """i32[G,1,1] per-history frontier sizes. Two single-axis reduces:
        Mosaic's layout inference Check-fails on a multi-axis keepdims
        reduce straight to [G,1,1]."""
        pc = jax.lax.population_count(T).astype(jnp.int32)
        return jnp.sum(jnp.sum(pc, axis=2, keepdims=True), axis=1,
                       keepdims=True)

    MAX_PAIRS = (cfg.rounds + 1) // 2

    def body(ln_ref, tg_ref, cm_ref, out_ref, T_s, dead_s, step_s, maxf_s,
             cfgs_s):
        b = pl.program_id(0)
        c = pl.program_id(1)
        NC = pl.num_programs(1)
        RC = cm_ref.shape[1]

        @pl.when(c == 0)
        def _init():
            rows = jax.lax.broadcasted_iota(jnp.int32, (G, Sp, W), 1)
            cols = jax.lax.broadcasted_iota(jnp.int32, (G, Sp, W), 2)
            T_s[...] = jnp.where((rows == init_row) & (cols == 0),
                                 jnp.uint32(1), jnp.uint32(0))
            dead_s[...] = jnp.zeros((G, 1, 1), jnp.int32)
            step_s[...] = jnp.full((G, 1, 1), -1, jnp.int32)
            maxf_s[...] = jnp.ones((G, 1, 1), jnp.int32)
            cfgs_s[...] = jnp.zeros((G, 1, 1), jnp.int32)

        # Bound the trip by the LONGEST history in the group: steps past
        # every member's length are pure pad and never execute (shorter
        # members' tail steps inside the trip stay guarded by is_pad).
        rg = ln_ref[b * G]
        for g in range(1, G):
            rg = jnp.maximum(rg, ln_ref[b * G + g])
        trip = jnp.clip(rg - c * RC, 0, RC)

        def step(i, carry):
            # dead carried as i32[G,1,1]: loop-carried rank-3 BOOL vectors
            # fail scf.for legalization in Mosaic.
            T, dead_i, dead_step, maxf, cfgs = carry
            r = c * RC + i
            t_raw = g3([tg_ref[b * G + g, r] for g in range(G)])
            is_pad = t_raw < 0                                 # [G,1,1]
            tv3 = jnp.maximum(t_raw, 0)
            allowed = allowed_mask(tv3)
            cm = cm_ref[:, i]                                  # [G,Sp,128]

            # Paired sweeps, loop while the pair's second sweep grew
            # ANYWHERE in the group (vector compare; one scalar cond per
            # step typical — see the r4 tuning notes). Pad histories'
            # colmask columns are zero, so their tables never change and
            # never extend the loop.
            T1 = closure(T, cm, allowed)
            T2 = closure(T1, cm, allowed)

            def wbody(st):
                Tw, _ch, pairs = st
                Ta = closure(Tw, cm, allowed)
                Tb = closure(Ta, cm, allowed)
                return Tb, jnp.any(Ta != Tb), pairs + 1

            def wcond(st):
                return st[1] & (st[2] < MAX_PAIRS)

            T, _c2, _p2 = jax.lax.while_loop(
                wcond, wbody, (T2, jnp.any(T1 != T2), jnp.int32(1)))
            n = popcounts(T)

            pruned = prune(T, tv3, allowed)
            T_new = jnp.where(is_pad, T, pruned)
            alive = popcounts(T_new) > 0
            died = ~is_pad & (dead_i == 0) & ~alive
            dead_i = dead_i | died.astype(jnp.int32)
            T_new = jnp.where(dead_i != 0, jnp.zeros_like(T_new), T_new)
            return (T_new, dead_i,
                    jnp.where(died & (dead_step < 0), r, dead_step),
                    jnp.maximum(maxf, n),
                    cfgs + jnp.where(is_pad, 0, n))

        init = (T_s[...], dead_s[...], step_s[...], maxf_s[...],
                cfgs_s[...])
        T, dead_i, dead_step, maxf, cfgs = jax.lax.fori_loop(0, trip, step,
                                                             init)
        T_s[...] = T
        dead_s[...] = dead_i
        step_s[...] = dead_step
        maxf_s[...] = maxf
        cfgs_s[...] = cfgs

        @pl.when(c == NC - 1)
        def _emit():
            for g in range(G):
                out_ref[5 * (b * G + g) + 0] = 1 - scalar_of(dead_i, g)
                out_ref[5 * (b * G + g) + 1] = jnp.int32(0)
                out_ref[5 * (b * G + g) + 2] = scalar_of(dead_step, g)
                out_ref[5 * (b * G + g) + 3] = scalar_of(maxf, g)
                out_ref[5 * (b * G + g) + 4] = scalar_of(cfgs, g)

    def bind(row):
        nonlocal init_row
        init_row = row
        return body

    return bind


def local_pallas_launcher_grouped(model: Model, cfg: DenseConfig, G: int,
                                  interpret: bool = False):
    """launch(B, R) for the grouped kernel; B must be a multiple of G."""
    max_k = limits().max_k_pallas
    if cfg.k_slots > max_k:
        raise ValueError(f"pallas kernel supports k_slots <= {max_k}, "
                         f"got {cfg.k_slots}")
    _require_converging_cap(cfg)
    Sp = max(8, (cfg.n_states + 7) // 8 * 8)
    W = 1 << (cfg.k_slots - 5)
    row = int(model.init_state()) + cfg.state_offset
    kernel = _kernel_body_grouped(cfg, G)(row)

    import functools

    @functools.lru_cache(maxsize=None)
    def launch(B: int, R: int):
        if B % G:
            raise ValueError(f"grouped launch: batch {B} % group {G} != 0")
        # The colmask block is G histories x RC steps x (Sp,128) tiles;
        # RC shrinks so the block stays ~2 MiB (like the per-history
        # kernel's) whatever the group size and state width.
        RC = _step_chunk(R, G, Sp)
        NC = (R + RC - 1) // RC
        R_pad = NC * RC

        def part_call(start: int, size: int):
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,   # lengths [size] + targets
                grid=(size // G, NC),
                in_specs=[
                    pl.BlockSpec((G, RC, Sp, 128),
                                 lambda b, c, ln_ref, tg_ref:
                                 (b + start // G, c, 0, 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=[pl.BlockSpec((5 * size,),
                                        lambda b, c, ln_ref, tg_ref: (0,),
                                        memory_space=pltpu.SMEM)],
                scratch_shapes=[
                    pltpu.VMEM((G, Sp, W), jnp.uint32),    # table carry
                    pltpu.VMEM((G, 1, 1), jnp.int32),      # dead
                    pltpu.VMEM((G, 1, 1), jnp.int32),      # dead_step
                    pltpu.VMEM((G, 1, 1), jnp.int32),      # max_frontier
                    pltpu.VMEM((G, 1, 1), jnp.int32),      # configs_explored
                ],
            )
            return pl.pallas_call(
                kernel, grid_spec=grid_spec,
                out_shape=[jax.ShapeDtypeStruct((5 * size,), jnp.int32)],
                interpret=interpret, name="wgl3_grouped")

        parts = launch_parts(B, R_pad, G)

        def run(ln, tg, cm):
            if R_pad != R:
                tg = jnp.pad(tg, ((0, 0), (0, R_pad - R)),
                             constant_values=-1)
                cm = jnp.pad(cm, ((0, 0), (0, R_pad - R), (0, 0), (0, 0)))
            return _run_parts(part_call, parts, ln, tg, cm).reshape(B, 5)

        # jtflow: packed wgl3.PACKED_FIELDS
        return instrument_kernel("wgl3-pallas-grouped", jax.jit(run))

    return launch


def make_batch_checker_pallas_grouped(model: Model, cfg: DenseConfig,
                                      group: int | None = None,
                                      interpret: bool = False):
    """Grouped-kernel twin of make_batch_checker_pallas. The batch is
    padded to a group multiple with all-pad histories (targets=-1) and
    results stripped, so any B works."""
    import functools

    G = group or limits().pallas_group
    prep = _cached_prep(model, cfg)
    launch = local_pallas_launcher_grouped(model, cfg, G, interpret)

    def check(slot_tabs, slot_active, targets):
        B, R = targets.shape
        B_pad = (B + G - 1) // G * G
        if B_pad != B:
            extra = B_pad - B
            slot_tabs = jnp.concatenate(
                [slot_tabs, jnp.zeros((extra,) + slot_tabs.shape[1:],
                                      slot_tabs.dtype)])
            slot_active = jnp.concatenate(
                [slot_active, jnp.zeros((extra,) + slot_active.shape[1:],
                                        slot_active.dtype)])
            targets = jnp.concatenate(
                [targets, jnp.full((extra, R), -1, targets.dtype)])
        colmask, tg, lengths = prep(slot_tabs, slot_active, targets)
        return launch(B_pad, R)(lengths, tg, colmask)[:B]

    return check


def cached_batch_checker_pallas_grouped(model: Model, cfg: DenseConfig,
                                        group: int | None = None,
                                        interpret: bool = False):
    G = group or limits().pallas_group
    key = ("pallas-grouped", model.cache_key(), cfg, G, interpret)
    if key not in _CACHE:
        _CACHE[key] = make_batch_checker_pallas_grouped(model, cfg, G,
                                                        interpret)
    return _CACHE[key]


_CACHE: dict[tuple, object] = {}


def cached_batch_checker_pallas(model: Model, cfg: DenseConfig,
                                interpret: bool = False):
    key = ("pallas", model.cache_key(), cfg, interpret)
    if key not in _CACHE:
        _CACHE[key] = make_batch_checker_pallas(model, cfg, interpret)
    return _CACHE[key]


# Scalar memory (SMEM) of one TPU v5e core: 1 MiB. Mosaic pads the
# minor dim of a 2-D SMEM array to 128 words, so a [B, 160] targets
# table fills B x 256 words (the 1024x150-op corpus in one launch is
# 1.02 MiB and the chip's compiler refuses it).
SMEM_LANE_PAD = 128
# One group's tables (G x W words per state row) that the grouped
# kernel keeps live in VMEM; measured by v5e compiles: G=16 fits at
# k_slots <= 14, G=8 at k=15, G=4 at k=16 (G=16 at k=15 runs out).
GROUP_VMEM_WORDS = 8192


def _step_chunk(R: int, G: int, Sp: int) -> int:
    """Return steps per colmask block: ~2 MiB of block whatever the group
    size and state width (G=1 is the per-history kernel)."""
    chunk = limits().pallas_step_chunk
    if G <= 1:
        return min(R, chunk)
    return min(R, max(8, chunk * 8 // (G * Sp)))


def smem_words(batch: int, r_pad: int) -> int:
    """int32 words one pallas launch keeps in SMEM: the scalar-prefetched
    targets table [batch, r_pad] with its lane padding, plus lengths[batch]
    and the flat 5*batch result block."""
    return batch * (-(-r_pad // SMEM_LANE_PAD) * SMEM_LANE_PAD + 6)


def launch_parts(B: int, r_pad: int, unit: int = 1) -> list[int]:
    """Split a B-history launch into parts of whole `unit`s (the group
    size) whose SMEM each fits limits().max_prefetch_pallas, as even as
    possible: 1024 histories at 160 steps run as two 512-history
    launches."""
    per = smem_words(1, r_pad)
    max_b = limits().max_prefetch_pallas // per // unit * unit
    if max_b < unit:
        raise ValueError(f"pallas launch of {unit} x {r_pad} steps exceeds "
                         f"SMEM ({unit * per} > "
                         f"{limits().max_prefetch_pallas} words)")
    units = B // unit
    n = -(-B // max_b)
    base, extra = divmod(units, n)
    return [(base + (i < extra)) * unit for i in range(n)]


def _run_parts(part_call, parts: list[int], ln, tg, cm):
    """One pallas_call per SMEM-sized part, in one jit: lengths and
    targets are sliced (they land in SMEM), the colmask is indexed in
    place by each part's block offset. Returns the flat i32[5*B]."""
    outs, start = [], 0
    for size in parts:
        outs.append(part_call(start, size)(
            ln[start:start + size], tg[start:start + size], cm)[0])
        start += size
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def pallas_feasible(cfg: DenseConfig | None,
                    n_steps: int | None = None,
                    batch: int | None = None,
                    group: int = 1) -> bool:
    """Does ONE launch of this shape fit the pallas kernel? k_slots <=
    max_k_pallas (the table stays a handful of VMEM tiles), steps <=
    max_r_pallas, and the launch's SMEM (smem_words, with the step axis
    padded as the kernel pads it) <= max_prefetch_pallas. `batch` None
    asks for the smallest launch (one group). Batches larger than one
    launch are split by launch_parts; use_pallas is the router's
    predicate."""
    lim = limits()
    if cfg is None or cfg.k_slots > lim.max_k_pallas:
        return False
    if n_steps is None:
        return True
    if n_steps > lim.max_r_pallas:
        return False
    sp = max(8, (cfg.n_states + 7) // 8 * 8)
    rc = _step_chunk(n_steps, group, sp)
    r_pad = -(-n_steps // rc) * rc
    b = max(group, 1) if batch is None else batch
    return smem_words(b, r_pad) <= lim.max_prefetch_pallas


def pallas_group_size(cfg: DenseConfig, n_steps: int | None,
                      batch: int | None) -> int:
    """Histories per program for this launch: the grouped kernel's G when
    the states fit one sublane tile (Sp=8), the batch holds a whole
    group, and one group fits VMEM (GROUP_VMEM_WORDS) and SMEM; else 1
    (the per-history kernel)."""
    G = limits().pallas_group
    W = 1 << (cfg.k_slots - 5)
    while G > 1 and G * W > GROUP_VMEM_WORDS:
        G //= 2
    sp = max(8, (cfg.n_states + 7) // 8 * 8)
    if (sp == 8 and G > 1 and batch is not None and batch >= G
            and pallas_feasible(cfg, n_steps, G, group=G)):
        return G
    return 1


def pallas_available() -> bool:
    """Compiled pallas runs only on a TPU backend (tests use
    interpret=True explicitly on CPU)."""
    return jax.default_backend() == "tpu"


def use_pallas(cfg: DenseConfig | None, n_steps: int | None = None) -> bool:
    """Production routing predicate: one history of this geometry fits
    the kernel (larger batches split into SMEM-sized launches) AND the
    backend is a TPU."""
    return pallas_feasible(cfg, n_steps) and pallas_available()


def check_batch_encoded_pallas(encs: Sequence[EncodedHistory],
                               model: Model | None = None,
                               interpret: bool = False) -> list[dict]:
    """Batch entry point mirroring wgl3.check_batch_encoded3."""
    from .wgl3 import assemble_batch_results, unpack_np

    if model is None:
        from ..models import CASRegister
        model = CASRegister()
    cfg, arrays, steps = batch_arrays3(encs, model)
    if not pallas_feasible(cfg, n_steps=arrays[2].shape[1]):
        raise ValueError(
            f"pallas infeasible for k_slots={cfg.k_slots}, "
            f"n_steps={arrays[2].shape[1]}")
    check = cached_batch_checker_pallas(model, cfg, interpret)
    return assemble_batch_results(unpack_np(check(*arrays)), steps, cfg)


def check_encoded_general(enc: EncodedHistory, model: Model,
                          f_cap: int = 256,
                          f_cap_max: int | None = None,
                          time_budget_s: float | None = None) -> dict:
    """The exact-verdict ladder for geometries OUTSIDE the dense budget
    (wide pending sets / huge values):

      1. resumable sort kernel, with f_cap capped so the per-step sort
         stays under sort_row_budget (~2M keys);
      2. if the live frontier outgrows that cap, the dense subset-lattice
         run CHUNKED with a budget override — per-step cost is 2^K bits
         but capacity is unconditionally exact, and small chunks keep
         each program short.

    Every rung is exact; there is no oracle fallback. The result carries
    a "kernel" key naming the rung that produced the verdict. When the
    geometry defeats every rung (frontier past every permissible f_cap
    AND a lattice too wide to sweep — seen at ~28 pending ops, where the
    dense table would be 2^31 cells), the verdict is the honest tri-state
    "unknown" with overflow=True, never a crash: the jepsen checker
    contract (and knossos' behavior at its own limits) is an
    indeterminate result, and merge_valid propagates it so the run exits
    nonzero."""
    import time as _time

    from . import wgl2, wgl3
    from .encode import encode_return_steps, reslot_events

    t0 = _time.monotonic()           # ONE clock for the whole ladder: the
    #                                  dense rung gets the REMAINING budget,
    #                                  so a check never spends ~2x the
    #                                  configured bound (ADVICE r2)
    tight = wgl2.sort_k_slots(enc)   # f_cap_max sizing must match the
    #                                  width the sort kernel really uses
    # A CHUNKED dense lattice under the relaxed 2^26-cell budget, when one
    # exists, beats the sort kernel's high rungs: past a few thousand live
    # configs each expansion round sorts f_cap*(k+1) keys, while the dense
    # sweep's bit-parallel cost is fixed — combinatorial frontiers (e.g. a
    # mutex history with m indeterminate acquires AND releases pending:
    # ~C(2m, m) reachable configs) DNF the sort ladder but sweep in
    # seconds. So cap the sort rungs early when dense-chunked is waiting.
    lim = limits()
    cfg_dense = wgl3.dense_config(model, tight, enc.max_value,
                                  budget=lim.dense_cell_budget_chunked)
    # Multi-device: the lattice-sharded sweep (parallel/lattice.py)
    # upgrades the dense rung — its cell budget scales with the device
    # count and each device sweeps 1/D of the table, so geometries the
    # single-device rung must refuse become checkable at all.
    cfg_lat = None
    if jax.device_count() > 1:
        from ..parallel.lattice import lattice_dense_config

        cfg_lat = lattice_dense_config(model, tight, enc.max_value,
                                       jax.device_count())
    cfg_sweep = cfg_lat if cfg_lat is not None else cfg_dense
    if f_cap_max is None:
        # The sort-row allocation fault is a worker-profile limit; other
        # backends take the sort kernel as far as memory goes.
        if pallas_available():
            f_cap_max = max(4096, min(1 << 20,
                                      lim.sort_row_budget // (tight + 1)))
        else:
            f_cap_max = 1 << 20
        if cfg_sweep is not None:
            # Stop the sort ladder where the dense sweep becomes cheaper:
            # a sort rung costs ~f_cap*(k+1) sorted keys per step, the
            # dense sweep a fixed ~cells bit-ops per step — PER DEVICE
            # when the lattice-sharded rung will run it, so wide
            # geometries route to the cheap sweep early instead of
            # burning the budget on huge sort rungs. (Only for the
            # computed default — an explicit caller f_cap_max stands.)
            cells = cfg_sweep.n_states * cfg_sweep.n_masks
            if cfg_lat is not None:
                cells //= jax.device_count()
            f_cap_max = min(f_cap_max, max(f_cap, cells // (tight + 1)))

    def dense_chunked(enc):
        # Remaining budget only (ADVICE r2: the fallback used to restart
        # the clock, spending up to 2x the configured bound). A launched
        # chunk cannot be preempted, so overshoot is bounded by ONE chunk;
        # with nothing left, don't start the rung at all.
        remaining = (None if time_budget_s is None else
                     time_budget_s - (_time.monotonic() - t0))
        if remaining is not None and remaining <= 0.5:
            return {"valid": "unknown", "survived": False, "overflow": True,
                    "dead_step": -1, "max_frontier": -1,
                    "configs_explored": -1, "op_count": enc.n_ops,
                    "f_cap": cfg_sweep.n_states * cfg_sweep.n_masks,
                    "escalations": 0, "kernel": "exhausted",
                    "error": f"sort ladder consumed the whole "
                             f"{time_budget_s:.0f}s budget; dense-chunked "
                             f"rung not started"}
        if enc.k_slots != tight:
            enc = reslot_events(enc, tight)
        rs = encode_return_steps(enc)
        # The lattice / pallas / XLA ladder lives in the KernelPlan
        # layer now (plan.dispatch_long — ONE copy shared with
        # run_long_dense); this rung only picks the geometry (the
        # lattice cfg when the mesh shards it, the relaxed chunked
        # budget otherwise) and threads the remaining budget.
        from ..plan import dispatch_long

        if cfg_lat is not None:
            from ..parallel.lattice import lattice_mesh

            out = dispatch_long(rs, model, cfg_lat,
                                lattice_mesh=lattice_mesh(),
                                time_budget_s=remaining)
        else:
            out = dispatch_long(rs, model, cfg_dense,
                                time_budget_s=remaining)
        out["op_count"] = enc.n_ops
        out["f_cap"] = cfg_sweep.n_states * cfg_sweep.n_masks
        out["escalations"] = 0
        return out

    try:
        # keep_death_checkpoint: zero-cost until death, and on an invalid
        # verdict it hands the witness rung the exact frontier nearest
        # the death point so no second search is needed
        # (checkers/witness.py reconstruct_witness_from_sort_checkpoint).
        out = wgl2.check_encoded_resumable(enc, model, f_cap=f_cap,
                                           f_cap_max=f_cap_max,
                                           time_budget_s=time_budget_s,
                                           keep_death_checkpoint=True)
        out["kernel"] = "wgl2-sort-resumable"
        return out
    except MemoryError as e:
        # Capacity OR time exhausted: the dense-chunked rung (no frontier
        # capacity at all) when one exists, else the honest tri-state.
        if cfg_sweep is None:
            return {"valid": "unknown", "survived": False, "overflow": True,
                    "dead_step": -1, "max_frontier": -1,
                    "op_count": enc.n_ops, "f_cap": f_cap_max,
                    "escalations": -1, "kernel": "exhausted",
                    "error": str(e)}
        return dense_chunked(enc)


def packed_batch_checker(model: Model, cfg: DenseConfig,
                         n_steps: int | None = None,
                         batch: int | None = None):
    """The SINGLE-DEVICE dense routing point, now a shim over the
    KernelPlan layer (plan/dispatch.py plan_dense_batch — one copy of
    the pallas-vs-XLA/grouped policy this function, the sharded router
    and the sched bucket launcher each used to carry; the grouped-
    kernel tuning notes live on its docstring). Returns
    (packed_check_fn, kernel_name). `shard=False` pins the local form:
    this entry is the deliberately-unsharded router (bench kernel arms,
    single-history launches) — multi-device callers go through
    plan_dense_batch / check_batch_encoded_auto, which shard the batch
    axis over the mesh."""
    from ..plan import plan_dense_batch, resolve

    p = plan_dense_batch(model, cfg, n_steps=n_steps, batch=batch,
                         shard=False)
    return resolve(p), p.label


def check_batch_encoded_auto(encs: Sequence[EncodedHistory],
                             model: Model | None = None
                             ) -> tuple[list[dict], str]:
    """Route a batch to the best dense backend for this platform; returns
    (per-history results, kernel_name — "mixed" when histories split
    across backends).

    The batch is PARTITIONED by per-history dense feasibility: one wide
    or huge-value history must not demote a whole corpus to sequential
    ladder runs — the feasible majority still goes through one batched
    launch.

    Tiny SINGLE histories on a live TPU backend route to the exact host
    oracle instead (VERDICT r3 item 5): below the crossover — measured
    per platform, ops/calibrate.py — the device dispatch+fetch round
    trip alone exceeds the oracle's whole runtime (tutorial-scale
    analyze, ~150 ops, is ~5 ms host against the platform's dispatch
    round trip). This is the SAME exact algorithm — not a soundness
    fallback — and batches never take it (batching amortizes the
    dispatch). The route is bounded both ways: wide-pending histories
    are excluded up front and a transition budget aborts into the device
    ladder (ADVICE r4 medium)."""
    from . import wgl3

    if model is None:
        from ..models import CASRegister
        model = CASRegister()
    if (len(encs) == 1 and pallas_available()
            and encs[0].n_events <= _oracle_crossover()
            and encs[0].max_pending <= limits().oracle_route_max_pending):
        # max_pending gate + transition budget (ADVICE r4 medium): the
        # frontier holds up to 2^pending masks per state, so a tiny-event
        # but wide-concurrency history could grind an exponential host
        # search. Wide histories and budget expiries take the capped
        # device ladder below instead — same verdicts, bounded cost.
        from ..checkers.oracle import OracleBudgetExceeded

        try:
            return ([_oracle_result(encs[0], model,
                                    limits().oracle_config_budget)],
                    "oracle-small-history")
        except OracleBudgetExceeded:
            pass
    dense_idx, general_idx = partition_dense(encs, model)

    results: list = [None] * len(encs)
    kernels: set[str] = set()
    if dense_idx:
        sub = [encs[i] for i in dense_idx]
        try:
            with phase("sched.pack"):
                cfg, steps, r_cap = wgl3.batch_steps3(sub, model)
        except ValueError:
            # Individually feasible but not under one SHARED geometry
            # (e.g. one history's k with another's value range): ladder
            # each — rare extreme, correctness over batching.
            general_idx = sorted(general_idx + dense_idx)
            dense_idx = []
        else:
            if r_cap > limits().long_scan_max:
                # Step count exceeds one scan program: host-driven chunked
                # sweeps, one history at a time — arrays never stacked or
                # transferred. On a live TPU the fused kernel runs in
                # launch-sized windows with the search state carried
                # between launches (check_steps3_long_pallas — the 100k-op
                # lane); elsewhere the XLA scan streams chunk by chunk.
                for i, s in zip(dense_idx, steps):
                    one = run_long_dense(s, model, cfg)
                    results[i] = one
                    kernels.add(one["kernel"])
            elif jax.device_count() > 1 and len(sub) > 1:
                # Multi-device: shard the batch axis over all devices —
                # the PRODUCTION multi-chip path (corpus / independent
                # keys ride it automatically; VERDICT r2 missing #1).
                from ..parallel.dense import check_steps_sharded

                batch_out, name = check_steps_sharded(
                    model, cfg, steps, r_cap)
                for i, one in zip(dense_idx, batch_out):
                    results[i] = one
                kernels.add(name)
            else:
                with phase("sched.pack"):
                    arrays = wgl3.stack_steps3(steps, r_cap)
                    check, name = packed_batch_checker(
                        model, cfg, n_steps=r_cap, batch=len(sub))
                with phase("sched.dispatch"):
                    dev = check(*arrays)
                with phase("sched.fetch_wait"):
                    fetched = np.asarray(dev)
                with phase("sched.unpack"):
                    batch_out = wgl3.assemble_batch_results(
                        wgl3.unpack_np(fetched), steps, cfg)
                    for i, one in zip(dense_idx, batch_out):
                        results[i] = one
                kernels.add(name)
    if general_idx:
        overflowed, too_long, top = _batch_general(encs, general_idx, model,
                                                   results, kernels)
        # The batched tiers PROVED capacities up to `top` overflow for
        # these: start the ladder past every dead rung.
        ladder_tail(encs, model, results, kernels, too_long,
                    [(i, LADDER_SEED_FACTOR * top) for i in overflowed])
    return results, (kernels.pop() if len(kernels) == 1 else "mixed")


def _oracle_crossover() -> int:
    """Active oracle-route crossover: a non-negative limits() value is
    authoritative (0 = route off; >0 = fixed); -1 (the default) defers to the per-platform measurement
    (ops/calibrate.py — dispatch floor x oracle throughput, persisted)."""
    fixed = limits().oracle_crossover_events
    if fixed >= 0:
        return fixed
    from .calibrate import get_calibration

    return get_calibration().crossover_events


def _oracle_result(enc: EncodedHistory, model: Model,
                   max_configs: int | None = None) -> dict:
    """Host-oracle run shaped like a kernel result (the schema of
    wgl3.assemble_batch_results — `valid`/`dead_step`/`overflow` agree
    field-for-field with the dense kernel; the search metrics
    `max_frontier`/`configs_explored` count the SAME quantities — live
    configs high-water mark and transition attempts — but can differ in
    value because the oracle's JIT closure regenerates beyond-boundary
    configs the dense table keeps, see tests/test_oracle.py's
    field-agreement test): dead_event (event index) translates to the v2
    kernel's return-step index by counting returns strictly before it.
    Raises OracleBudgetExceeded past `max_configs` transition attempts —
    the router falls back to the device ladder."""
    import numpy as np

    from ..checkers.oracle import check_events_oracle
    from .encode import EV_RETURN

    from . import wgl3

    res = check_events_oracle(enc, model, max_configs)
    if res.dead_event < 0:
        dead_step = -1
    else:
        ev = np.asarray(enc.events[:res.dead_event, 0])
        dead_step = int((ev == EV_RETURN).sum())
    # table_cells: schema parity with assemble_batch_results (the
    # independent checker reads it as the exact path's capacity). The
    # oracle has no dense table; report the cells the dense kernel WOULD
    # have used, or 0 for a dense-infeasible tiny history (the oracle is
    # exact either way).
    cfg = wgl3.dense_config(model, wgl3.tight_k_slots(enc), enc.max_value)
    out = {
        "survived": bool(res.valid), "overflow": False,
        "dead_step": dead_step, "max_frontier": res.max_frontier,
        "configs_explored": int(res.configs_explored),
        "valid": res.valid, "op_count": enc.n_ops,
        "table_cells": 0 if cfg is None else cfg.n_states * cfg.n_masks,
        "kernel": "oracle-small-history",
    }
    record_check_result(out)
    return out


# First ladder rung after the batched tiers prove `top` overflows — shared
# by check_batch_encoded_auto and the independent checker's f_cap_floor
# threading (checkers/independent.py) so the seeding policy has one copy.
LADDER_SEED_FACTOR = 4


# -- routing policy shared with the corpus scheduler (sched/engine.py) -----
# The scheduler changes HOW dense batches are padded and launched, never
# WHICH kernel checks what: partition criteria, the long-history sweep
# dispatch, and the general-lane ladder tail live here, in exactly one
# copy, so the two batched entry points cannot drift.

def partition_dense(encs: Sequence[EncodedHistory], model: Model
                    ) -> tuple[list[int], list[int]]:
    """Per-history dense feasibility split: (dense_idx, general_idx)."""
    from . import wgl3

    dense_idx, general_idx = [], []
    for i, e in enumerate(encs):
        ok = dense_config(model, wgl3.tight_k_slots(e), e.max_value)
        (dense_idx if ok is not None else general_idx).append(i)
    return dense_idx, general_idx


def run_long_dense(rs, model: Model, cfg: DenseConfig) -> dict:
    """One dense-feasible history whose step count exceeds a scan
    program: the host-chunked sweep, routed through the KernelPlan
    layer (plan.dispatch_long — fused pallas windows on a live TPU,
    the XLA chunk loop elsewhere, the sparse engine where the density
    plan engages), result normalized to the batched schema
    (op_count/table_cells/kernel)."""
    from ..plan import dispatch_long

    one = dispatch_long(rs, model, cfg)
    one["op_count"] = rs.n_ops
    one["table_cells"] = cfg.n_states * cfg.n_masks
    return one


def ladder_tail(encs, model: Model, results: list, kernels: set,
                too_long: Sequence[int],
                overflow_seeds: Sequence[tuple[int, int]]) -> None:
    """The general lane's per-history tail after the batched sort tiers:
    too-long histories ladder from scratch; tier-proven overflows ladder
    seeded past every capacity the tiers showed dead."""
    for i in too_long:
        one = check_encoded_general(encs[i], model)
        results[i] = one
        kernels.add(one["kernel"])
    for i, seed in overflow_seeds:
        one = check_encoded_general(encs[i], model, f_cap=seed)
        results[i] = one
        kernels.add(one["kernel"])


# Batched-tier capacities for the non-dense pass. Start small: sort cost
# per launch is linear in f_cap (measured on a 256-history fifo corpus:
# 3.2 s at f_cap=256 vs 1.1 s at 64), typical frontiers are tiny, and an
# overflowed history re-batches at the next tier — still one launch per
# tier, vs ~0.5 s per history for a per-history ladder run.
GENERAL_TIERS = (64, 256, 1024)


def _batch_general(encs, idxs, model, results, kernels, f_cap: int = 256
                   ) -> tuple[list[int], list[int], int]:
    """Batched pass for the NON-dense partition of a batch (wide pending
    sets / huge-value states — queue and multi-register corpora live
    here): vmapped sort-kernel launches over a shared geometry instead of
    a sequential per-history ladder, escalating the frontier capacity in
    BATCHED tiers (GENERAL_TIERS, extended to cover the caller's f_cap).
    Exact verdicts (survived, or dead without overflow — soundness
    argument in ops/wgl2.py) land in `results`; returns (overflowed,
    too_long, top_tier): `overflowed` stayed "unknown" at every tier,
    `too_long` exceed one scan program (limits().long_scan_max) and were never
    launched — both must ladder per history. Launches are chunked so
    batch*f_cap*(k_slots+1) stays inside the tested-good sort-row budget
    (limits().sort_row_budget — the worker profile faults past ~2M rows)
    AND the stacked slot tables stay a few hundred MB."""
    import jax.numpy as jnp

    from . import wgl, wgl2, wgl3
    from .encode import encode_return_steps, reslot_events

    sub = [(i, encs[i]) for i in idxs]
    k = max(wgl2.sort_k_slots(e) for _, e in sub)
    max_value = max(e.max_value for _, e in sub)
    steps, too_long = [], []
    for i, e in sub:
        rs = encode_return_steps(
            reslot_events(e, k) if e.k_slots != k else e)
        if rs.n_steps > limits().long_scan_max:
            too_long.append(i)   # needs host-chunked scans, not one program
        else:
            steps.append((i, rs))
    if not steps:
        return [], too_long, GENERAL_TIERS[-1]
    r_cap = min(wgl3.step_bucket(max(1, max(s.n_steps for _, s in steps))),
                limits().long_scan_max)
    # Every GENERAL_TIERS rung runs regardless of the caller's f_cap (the
    # point of tiering is re-batching overflows instead of laddering them
    # per history); f_cap joins as an extra rung when it is larger. No
    # tier may exceed the sort-row budget for ONE history — chunking
    # shrinks the batch, never a single lane's f_cap*(k+1) rows.
    cap_max = max(GENERAL_TIERS[0], limits().sort_row_budget // (k + 1))
    tiers = sorted({min(t, cap_max) for t in (*GENERAL_TIERS, f_cap)})

    n_dev = jax.device_count()

    def launch(tier_steps, tier_cap):
        cfg = wgl2.make_config(model, k, tier_cap, max_value)
        lim = limits()
        chunk = max(1, min(
            lim.sort_row_budget // (tier_cap * (k + 1)),
            lim.stack_element_budget // max(1, r_cap * (k + 1))))
        sharded = n_dev > 1 and chunk >= n_dev
        from ..plan import build_plan, resolve

        if sharded:
            # Multi-device: the NON-dense production path (queue /
            # multi-register corpora) shards its batch axis too, like the
            # dense path (VERDICT r2 missing #1) — family
            # wgl2-sort-sharded, through the plan spine (mesh-keyed).
            from ..parallel.dense import batch_mesh

            check = resolve(build_plan("wgl2-sort-sharded", model, cfg,
                                       mesh=batch_mesh(),
                                       label="wgl2-sort-sharded"))
        else:
            check = resolve(build_plan("wgl2-batch", model, cfg,
                                       label="wgl2-sort-batched"))
        overflowed = []
        for c0 in range(0, len(tier_steps), chunk):
            part = tier_steps[c0:c0 + chunk]
            # Bucket the batch axis too: bounded recompiles across corpora
            # of varying size (pad histories are all-pad scans — no work);
            # sharded launches additionally pad to the device count.
            b_cap = min(wgl3.step_bucket(len(part), floor=8), chunk)
            if sharded:
                b_cap = (b_cap + n_dev - 1) // n_dev * n_dev
            with phase("sched.pack"):
                padded = [s.padded_to(r_cap) for _, s in part]
                tabs = np.zeros((b_cap,) + padded[0].slot_tabs.shape,
                                np.int32)
                act = np.zeros((b_cap,) + padded[0].slot_active.shape, bool)
                tgt = np.full((b_cap, r_cap), -1, np.int32)
                for j, p in enumerate(padded):
                    tabs[j] = p.slot_tabs
                    act[j] = p.slot_active
                    tgt[j] = p.targets
            with phase("sched.dispatch"):
                dev = check(jnp.asarray(tabs), jnp.asarray(act),
                            jnp.asarray(tgt))
            with phase("sched.fetch_wait"):
                out = {name: np.asarray(v) for name, v in dev.items()}
            for j, (i, s) in enumerate(part):
                one = {name: out[name][j].item() for name in out}
                v = wgl.verdict(one)
                if v == "unknown":
                    overflowed.append((i, s))
                    continue
                results[i] = {
                    "valid": v, "survived": one["survived"],
                    "overflow": one["overflow"],
                    "dead_step": one["dead_step"],
                    "max_frontier": one["max_frontier"], "op_count": s.n_ops,
                    "f_cap": tier_cap, "escalations": 0,
                    "kernel": "wgl2-sort-batched",
                }
                record_check_result(results[i])
                kernels.add("wgl2-sort-batched")
        return overflowed

    remaining = steps
    for tier_cap in tiers:
        remaining = launch(remaining, tier_cap)
        if not remaining:
            break
    return [i for i, _ in remaining], too_long, tiers[-1]
