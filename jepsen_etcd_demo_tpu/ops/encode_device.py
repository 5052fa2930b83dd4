"""Device-side history encoding: the return-major table built ON device.

The host encoder (ops/encode.py encode_return_steps) materializes the
packed slot-table tensor — R*(K*5+1) int32 cells per history — on the
host and ships the WHOLE thing across the host->device boundary on
every launch. The compact event stream it derives from (events[E, 6],
roughly K times smaller) is the real information content; everything
else is a deterministic expansion. This module is that expansion as a
jittable XLA program: the event tensor crosses once, and the slot-table
snapshot per return step is rebuilt on-device, so the packed-table H2D
disappears from the dispatch critical path and the encode fuses into
the launch pipeline (plan/dispatch.py LaunchPipeline).

Routing lives behind ``limits().encode_mode`` (ops/limits.py): 0 = auto
(device on the mesh-sharded batch lane, host elsewhere), 1 = host
always, 2 = device whenever the geometry fits. Both the post-hoc
encoder and the streaming ``IncrementalEncoder`` prefix route through
``ops.encode.encode_return_steps``, so one knob governs every path.

Bit-identity contract: for any EncodedHistory, the first n_steps rows
of the device output equal ``encode_return_steps(enc)`` exactly, and
the padded tail equals ``ReturnSteps.padded_to`` (tabs 0, active False,
targets -1) — all arithmetic is int32/bool, no floating point, so the
mirror is exact by construction and tests/test_pod_scaling.py pins it
with golden + fuzz differentials (crashed-op pinning and LIFO slot
reuse included).

Static shapes: the kernel compiles per (k_slots, e_cap, r_cap). Event
capacity buckets through the same {2^k, 1.5*2^k} ladder as the step
axis (wgl3.step_bucket) so ragged corpora share compiled shapes.
"""

from __future__ import annotations

import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import get_metrics, instrument_kernel
from .encode import (EV_INVOKE, EV_RETURN, EVENT_WIDTH, EncodedHistory,
                     ReturnSteps)

_CACHE: dict[tuple, Any] = {}

#: Floor of the event-axis capacity buckets. Events per history are
#: bounded by 2x the return count plus open invokes, so the event floor
#: tracks 2x the step-bucket floor's scale.
EVENT_BUCKET_FLOOR = 32


def event_bucket(n_events: int, floor: int = EVENT_BUCKET_FLOOR) -> int:
    """{2^k, 1.5*2^k} capacity bucket for the event axis — the event-
    tensor twin of the scheduler's step-length buckets, so nearby
    history sizes share one compiled encoder."""
    from . import wgl3

    return wgl3.step_bucket(max(1, int(n_events)), floor=floor)


def returns_count(enc: EncodedHistory) -> int:
    """Return-step count straight from the event stream (what
    encode_return_steps would report as n_steps) — no table expansion."""
    if enc.n_events == 0:
        return 0
    ev = np.asarray(enc.events[: enc.n_events])
    return int((ev[:, 0] == EV_RETURN).sum())


def _encode_fn(k_slots: int, e_cap: int, r_cap: int):
    """The un-jitted single-history encoder body:
    events i32[e_cap, 6] -> (slot_tabs i32[r_cap, K, 4],
    slot_active bool[r_cap, K], targets i32[r_cap]).

    Mirrors ops.encode.encode_return_steps' vectorized host algorithm
    term-for-term (one-hot cumsums, running last-invoke positions, the
    strictly-before return count), with two deviations forced by static
    shapes, both masked by `valid`: return positions are extracted with
    a fixed-size nonzero (fill rows gather event 0 and are zeroed), and
    the [r_cap] tail beyond the real return count reproduces
    ReturnSteps.padded_to's all-pad rows."""

    def encode(events):
        kinds = events[:, 0]
        slots = events[:, 1]
        sid = jnp.arange(k_slots, dtype=jnp.int32)
        is_inv = kinds == EV_INVOKE
        is_ret = kinds == EV_RETURN
        inv_oh = is_inv[:, None] & (slots[:, None] == sid)
        ret_oh = is_ret[:, None] & (slots[:, None] == sid)
        inv_cum = jnp.cumsum(inv_oh.astype(jnp.int32), axis=0)
        ret_cum = jnp.cumsum(ret_oh.astype(jnp.int32), axis=0)
        pos = jnp.arange(e_cap, dtype=jnp.int32)
        # Last invoke position of each slot at-or-before each event
        # position (host: np.maximum.accumulate over the masked iota).
        last_inv = jax.lax.cummax(
            jnp.where(inv_oh, pos[:, None], -1), axis=0)
        (ret_pos,) = jnp.nonzero(is_ret, size=r_cap, fill_value=0)
        n_ret = jnp.sum(is_ret.astype(jnp.int32))
        valid = jnp.arange(r_cap, dtype=jnp.int32) < n_ret
        # Event p is a return, so "invokes before p" == inv_cum[p];
        # "returns strictly before p" excludes p's own return.
        active = valid[:, None] & (
            inv_cum[ret_pos]
            > (ret_cum[ret_pos] - ret_oh[ret_pos].astype(jnp.int32)))
        last = last_inv[ret_pos]
        tabs = jnp.where(
            (last[:, :, None] >= 0) & valid[:, None, None],
            events[jnp.maximum(last, 0)][:, :, 2:6], 0).astype(jnp.int32)
        targets = jnp.where(valid, slots[ret_pos], -1).astype(jnp.int32)
        return tabs, active, targets

    return encode


def cached_device_encoder(k_slots: int, e_cap: int, r_cap: int):
    """Jitted single-history device encoder for one (K, E, R) geometry,
    instrumented for compile/execute attribution like every production
    kernel (the encoder must not be a telemetry blind spot)."""
    key = ("encode", k_slots, e_cap, r_cap)
    if key not in _CACHE:
        _CACHE[key] = instrument_kernel(
            "wgl3-encode", jax.jit(_encode_fn(k_slots, e_cap, r_cap)))
    return _CACHE[key]


def stack_events(encs: Sequence[EncodedHistory], e_cap: int):
    """Host-side half of the batched device encode: pad every event
    stream to the shared capacity, stack to i32[B, e_cap, 6], transfer
    (the ONLY per-launch H2D of the device-encode lane — ~K times
    smaller than the packed table it replaces)."""
    ev = np.stack([e.padded_to(e_cap).events for e in encs])
    nbytes = int(ev.nbytes)
    get_metrics().counter("wgl.h2d_bytes").add(nbytes)
    return jnp.asarray(ev)


def encode_return_steps_device(enc: EncodedHistory,
                               e_cap: int | None = None,
                               r_cap: int | None = None) -> ReturnSteps:
    """Single-history device encode, fetched back as a host ReturnSteps
    bit-identical to ``encode_return_steps(enc)`` (the encode_mode=2
    routing target and the differential-test subject). `r_cap` pads the
    compiled step axis; the result is trimmed back to the real return
    count so downstream shapes match the host encoder's exactly."""
    t_enc = time.monotonic()
    k = enc.k_slots
    n_ret = returns_count(enc)
    if n_ret == 0:
        return ReturnSteps(
            slot_tabs=np.zeros((0, k, 4), np.int32),
            slot_active=np.zeros((0, k), bool),
            targets=np.zeros((0,), np.int32),
            n_steps=0, n_ops=enc.n_ops, k_slots=k,
            max_pending=enc.max_pending, max_value=enc.max_value)
    if e_cap is None:
        e_cap = event_bucket(enc.n_events)
    if r_cap is None:
        from . import wgl3

        r_cap = wgl3.step_bucket(n_ret, floor=EVENT_BUCKET_FLOOR)
    fn = cached_device_encoder(k, e_cap, r_cap)
    ev_dev = stack_events([enc], e_cap)[0]
    tabs, act, tgt = (np.asarray(x) for x in fn(ev_dev))
    get_metrics().counter("encode.encode_s").add(time.monotonic() - t_enc)
    return ReturnSteps(
        slot_tabs=tabs[:n_ret], slot_active=act[:n_ret],
        targets=tgt[:n_ret].astype(np.int32),
        n_steps=n_ret, n_ops=enc.n_ops, k_slots=k,
        max_pending=enc.max_pending, max_value=enc.max_value)


def device_encode_feasible(enc: EncodedHistory) -> bool:
    """Whether the device encoder can take this history at all: the
    event stream must be non-degenerate and the one-hot expansion
    (e_cap * k_slots cells) must stay far inside the element budget a
    single launch is allowed to stack."""
    from .limits import limits

    if enc.n_events == 0:
        return False
    e_cap = event_bucket(enc.n_events)
    return e_cap * max(1, enc.k_slots) <= limits().stack_element_budget
