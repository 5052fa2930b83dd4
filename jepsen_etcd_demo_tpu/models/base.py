"""Model protocol: a deterministic state machine stepped by linearized ops.

Equivalent of knossos.model's Model/step seam (exercised by the reference at
src/jepsen/etcdemo.clj:117). A step either yields a successor state or is
illegal (the knossos "inconsistent" result); the checker prunes illegal
transitions from candidate linearization orders.
"""

from __future__ import annotations

import abc
from typing import Tuple


class Model(abc.ABC):
    """A state machine over int32 scalar states.

    States are int32 scalars so a search frontier is a flat int32 vector.
    Models with richer state must encode it into one int32 (or a future
    vector-state extension of the kernel).
    """

    name: str = "model"

    # Packing: when every reachable state is bounded by the values appearing
    # in the history (register-like models), (state, linearized-mask) can
    # live in ONE uint32 sort key — a payload-free single-key dedup in the
    # checker. `packable_states=True` opts in; `state_offset` maps the
    # smallest state (NIL=-1) to 0. The actual bit width is derived from
    # each history's real values via pack_bits(), NEVER from an assumed
    # value range (any int32 value is legal in a history, encode.py:46).
    packable_states: bool = False
    state_offset: int = 0

    def state_bound(self, max_value: int) -> int:
        """Largest shifted state index reachable, given the largest value
        encoded in the history (shift = state_offset, so the result is the
        top ROW index of a dense state table / top packed-key value).

        The reachable range is {init_state()} ∪ history values — the initial
        state counts even when no history value comes near it (a large
        `initial` that silently wrapped into mask bits was a reproduced
        soundness bug). Negative values never reach here: the encoder
        rejects them (NIL=-1 is a reserved sentinel, encode.py). Single
        source of truth for BOTH the packed sort-key dedup (wgl2) and the
        dense lattice table (wgl3)."""
        return max(int(max_value), int(self.init_state())) + self.state_offset

    def pack_bits(self, max_value: int) -> int:
        """Bits needed to pack any reachable state, given the largest value
        encoded in the history; 0 = not packable."""
        if not self.packable_states:
            return 0
        return max(1, self.state_bound(max_value).bit_length())

    def cache_key(self) -> tuple:
        """Hashable identity for jit-compilation caches. Two models with equal
        cache keys must have identical step semantics."""
        return (self.name, self.init_state())

    def prepare_history(self, history):
        """Model-level op translation applied before encoding (e.g. the
        mutex model rewrites acquire/release into CAS ops). Identity by
        default; must return Ops encode_invocation accepts."""
        return history

    def encode_invocation(self, f_name: str, invoke_value, ok_value,
                          status: str) -> Tuple[int, int, int, int]:
        """Op-language codec: map one paired invocation to the (f, a1, a2,
        rv) event-row fields the step functions consume. `ok_value` is the
        completion's value for OK *and* INFO completions (an indeterminate
        op may still carry the value it tried to take), None otherwise.
        Default: the register language (read/write/cas — the reference's
        op set, src/jepsen/etcdemo.clj:67-69). Models with a different op
        language override this; by convention code F_READ must be reserved
        for pure observations (the encoder drops indeterminate F_READ ops
        as constraint-free, ops/encode.py)."""
        from ..ops.encode import register_fields

        return register_fields(f_name, invoke_value, ok_value, status)

    def describe_op(self, f: int, a1: int, a2: int, rv: int) -> str:
        """Human-readable rendering of an encoded op (witness artifacts,
        checkers/witness.py). Default: the register language."""
        from ..ops.encode import NIL, F_READ, F_WRITE, F_CAS

        if f == F_READ:
            return f"read -> {'nil' if rv == NIL else rv}"
        if f == F_WRITE:
            return f"write({a1})"
        if f == F_CAS:
            return f"cas({a1} -> {a2})"
        return f"op({f}, {a1}, {a2}, {rv})"

    def translates(self) -> bool:
        """Whether prepare_history rewrites histories (the mutex and
        queue models): their ops are then read only after it has run."""
        return type(self).prepare_history is not Model.prepare_history

    @abc.abstractmethod
    def init_state(self) -> int:
        ...

    @abc.abstractmethod
    def step_py(self, state: int, f: int, a1: int, a2: int, rv: int
                ) -> Tuple[bool, int]:
        """Python-scalar step: (legal, next_state)."""

    @abc.abstractmethod
    def step(self, state, f, a1, a2, rv):
        """Branchless array step: (legal, next_state).

        Must be expressible with arithmetic/where only (no data-dependent
        Python control flow) so it vmaps and compiles on TPU.
        """
