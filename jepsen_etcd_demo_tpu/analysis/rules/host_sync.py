"""JTL103 host-sync-in-loop: device fetches hiding inside chunk loops.

The chunked sweeps stay fast because dispatch is asynchronous: the host
loop enqueues chunk N+1 while the device runs chunk N (PR 2's
pipelining; PR 5's streaming overlap). One ``.item()`` /
``np.asarray(carry...)`` / ``block_until_ready()`` inside such a loop
serializes the whole pipeline — every iteration round-trips the
device. BENCH rounds attributed multi-second regressions to exactly
this shape, where each fetch cost a device round trip.

Deliberate bounded fetches exist (the death polls every
``long_scan_poll`` chunks — the fail-fast contract) and must carry an
inline suppression WITH justification; the suppression is the
documentation.

Heuristics (documented in doc/analysis.md): ``.block_until_ready()``
always flags in a loop; ``np.asarray`` / ``np.array`` / ``bool/int/
float`` / ``.item()`` flag only when their operand source mentions a
device-carry hint (``carry``/``dead``/``overflow``/``jnp``) — plain
numpy post-processing loops stay silent.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator

from ..astutil import call_args_source, in_loop
from ..core import KERNEL_SCOPES, ModuleSource, Rule, register
from ..findings import Finding

_DEVICE_HINT = re.compile(r"\bcarry\b|\bdead\b|\boverflow\b|\bjnp\b")
_NP_FETCHES = ("numpy.asarray", "numpy.array")
_CAST_BUILTINS = ("bool", "int", "float")


@register
class HostSyncInLoopRule(Rule):
    id = "JTL103"
    name = "host-sync-in-loop"
    scopes = KERNEL_SCOPES
    rationale = (
        "Async dispatch is the chunk pipeline's whole win (PR 2/PR 5); "
        "a per-iteration host fetch serializes it — one device round "
        "trip per chunk.")
    hint = ("hoist the fetch out of the loop, batch it (one packed "
            "fetch at the end), or bound it (poll every N chunks) and "
            "suppress with the justification inline")

    def check(self, mod: ModuleSource) -> Iterator[Finding]:
        for node in mod.walk_nodes():
            if not (isinstance(node, ast.Call) and in_loop(node)):
                continue
            if isinstance(node.func, ast.Attribute):
                if node.func.attr == "block_until_ready":
                    yield mod.finding(
                        self, node,
                        "block_until_ready() inside a loop — "
                        "serializes every iteration on the device")
                    continue
                if node.func.attr == "item" and _DEVICE_HINT.search(
                        call_args_source(node.func.value, mod.text)):
                    yield mod.finding(
                        self, node,
                        ".item() on a device value inside a loop — a "
                        "blocking per-iteration D2H fetch")
                    continue
            origin = mod.imports.resolve(node.func)
            if origin is None:
                continue
            arg_src = " ".join(call_args_source(a, mod.text)
                               for a in node.args)
            if origin in _NP_FETCHES and _DEVICE_HINT.search(arg_src):
                yield mod.finding(
                    self, node,
                    f"np.{origin.rsplit('.', 1)[-1]}(...) on a device "
                    f"value inside a loop — a blocking per-iteration "
                    f"D2H fetch")
            elif origin in _CAST_BUILTINS and _DEVICE_HINT.search(arg_src) \
                    and not any(isinstance(a, ast.Call)
                                for a in node.args):
                # bool(np.asarray(x)) reports at the inner call only.
                yield mod.finding(
                    self, node,
                    f"{origin}() on a device value inside a loop — a "
                    f"blocking per-iteration D2H fetch")
