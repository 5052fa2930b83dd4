"""Fleet-wide compile-cache pre-warm (ISSUE 17 tentpole d).

Cold XLA compiles are the one latency the pod pipeline cannot overlap
away: the FIRST launch of every (kernel family, geometry) pays seconds
of compile on the dispatch critical path, once per process — multiplied
across a fleet, once per host. The persistent compilation cache
(sched/compile_cache.py) already makes compiles shareable across
processes; what was missing is a way to FILL it ahead of traffic.

``warmup_plans`` replays a plan-family corpus — the dense sharded
checker and the device-side encoder over the bucket geometries the
scheduler actually launches ({2^k, 1.5*2^k} step rungs at the tuned
floors, batch buckets padded to the mesh multiple) — through the
persistent cache with all-pad inputs (targets=-1: zero search work,
full compile + one execute each). Run it from one blessed host
(`jepsen-tpu warmup`) and every other host's first real launch becomes
a disk-cache hit; the serve daemon and campaign runner call the same
function at startup (one cheap rung) so a cold store never puts a
compile on a request's critical path.

The report is ledger-armed: every warmup launch runs under an obs
capture, and the returned record carries the zeros-never-absent
``ledger`` object the bench contract requires
(tools/bench_compare.py check_ledger_record — smoked by tier-1).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Optional

from .compile_cache import enable_persistent_cache

#: Env kill switch for the serve/campaign startup hooks (the explicit
#: CLI verb ignores it — asking for a warmup means wanting one).
NO_WARMUP_ENV = "JEPSEN_TPU_NO_WARMUP"


def step_rungs(n: int, floor: Optional[int] = None) -> list[int]:
    """The first `n` rungs of the {2^k, 1.5*2^k} step-bucket ladder the
    scheduler launches at (wgl3.step_bucket from the tuned floor) — the
    geometries worth pre-compiling."""
    from ..ops import wgl3
    from ..ops.limits import limits

    if floor is None:
        floor = limits().step_bucket_floor
    rungs, r = [], floor
    while len(rungs) < n:
        rungs.append(r)
        nxt = wgl3.step_bucket(r + 1, floor=floor)
        if nxt <= r:
            break
        r = nxt
    return rungs


def warmup_plans(model=None, mesh=None, *, k_slots: int = 16,
                 rungs: int = 2, max_value: int = 8,
                 encoder: bool = True) -> dict[str, Any]:
    """Pre-compile the plan-family corpus for this platform into the
    persistent XLA cache. Returns the warmup record: per-family launch
    labels, compile/execute seconds (the ledger object), wall, and the
    active cache directory (None when the cache is disabled)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import obs
    from ..models import CASRegister
    from ..obs import ledger as obs_ledger
    from ..ops import wgl3
    from ..ops.encode import EVENT_WIDTH
    from ..ops.limits import limits
    from ..parallel import dense as pdense
    from ..plan import plan_dense_batch, resolve

    t0 = time.monotonic()
    cache_dir = enable_persistent_cache()
    if model is None:
        model = CASRegister()
    if mesh is None:
        mesh = pdense.batch_mesh()
    cfg = wgl3.dense_config(model, k_slots, max_value)
    if cfg is None:
        raise ValueError(
            f"dense kernel infeasible at k_slots={k_slots} "
            f"max_value={max_value} — nothing to warm")
    lim = limits()
    families: list[str] = []
    launches = 0
    with obs.capture() as cap:
        for r in step_rungs(max(1, rungs)):
            mult = pdense.batch_multiple(model, cfg, mesh, n_steps=r,
                                         batch=lim.batch_bucket_floor)
            b = (wgl3.step_bucket(1, floor=lim.batch_bucket_floor)
                 + mult - 1) // mult * mult
            p = plan_dense_batch(model, cfg, n_steps=r, batch=b,
                                 mesh=mesh)
            check = resolve(p)
            lctx = obs_ledger.plan_context(p)
            lctx.update(batch_real=0, batch_padded=b, steps_real=0,
                        steps_padded=b * r)
            # All-pad inputs: targets=-1 rows are zero search work, so
            # the launch is almost pure compile — exactly what a warmup
            # wants on the ledger.
            tabs = np.zeros((b, r, cfg.k_slots, 4), np.int32)
            act = np.zeros((b, r, cfg.k_slots), bool)
            tgt = np.full((b, r), -1, np.int32)
            with obs_ledger.launch_context(**lctx):
                # jtlint: disable=JTL103 -- warmup wants the block: each
                # rung's fetch materializes its compile into the
                # persistent cache before the next rung is measured
                np.asarray(check(jnp.asarray(tabs), jnp.asarray(act),
                                 jnp.asarray(tgt)))
            families.append(p.label)
            launches += 1
            if encoder and lim.encode_mode != 1:
                from ..ops import encode_device

                e_cap = encode_device.event_bucket(2 * r)
                if e_cap * cfg.k_slots <= lim.stack_element_budget:
                    ev = np.zeros((b, e_cap, EVENT_WIDTH), np.int32)
                    ev[:, :, 0] = 2          # EV_PAD
                    enc_fn = pdense.sharded_device_encoder(
                        cfg.k_slots, e_cap, r, mesh)
                    with obs_ledger.launch_context(**lctx):
                        for a in enc_fn(jnp.asarray(ev)):
                            np.asarray(a)
                    families.append("wgl3-encode-sharded")
                    launches += 1
        led = obs.ledger_stats(cap.metrics)
    return {
        "value": launches,
        "backend": jax.default_backend(),
        "cache_dir": cache_dir,
        "mesh_shape": dict(mesh.shape),
        "families": sorted(set(families)),
        "launches": launches,
        "wall_s": round(time.monotonic() - t0, 4),
        "ledger": led,
    }


def startup_warmup(*, source: str = "startup") -> Optional[dict]:
    """The serve/campaign startup hook: one cheap rung through
    warmup_plans, swallowing every failure (a warmup must never take a
    daemon down) and honoring the JEPSEN_TPU_NO_WARMUP kill switch.
    Returns the warmup record, or None when skipped/failed."""
    if os.environ.get(NO_WARMUP_ENV):
        return None
    try:
        rec = warmup_plans(rungs=1)
    except Exception as e:   # never fatal — warmup is an optimization
        print(f"warmup ({source}): skipped ({type(e).__name__}: {e})",
              file=sys.stderr)
        return None
    # stderr: the serve daemon's stdout is a line-JSON protocol (the
    # ready record must be the first line a supervisor reads).
    print(f"WARMUP {json.dumps(rec, sort_keys=True)}", file=sys.stderr)
    return rec
