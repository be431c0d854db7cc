"""Device time per round stage and host time per driver span, read from
the names the program puts into its compiled scan and its trace.

The program runs each stage of its round under a ``jax.named_scope``
(``paota.schedule``, ``paota.stats``, ``paota.power``, ``paota.superpose``,
``paota.train``, ``paota.carry_write``): the scope reaches every
instruction of the compiled scan as its ``op_name`` metadata. A device
event of the trace is named by its instruction, so the compiled HLO text
maps each event to its stage (``scope_map``). The driver's host work in
``advance`` is marked by ``paota.advance`` and, inside it,
``paota.dispatch``, ``paota.fetch`` and ``paota.rows`` profiler spans on
the trace's clock (``host_span_seconds``).

``stage_map(ctx)`` takes the compiled text from the run's live driver
(the readers' context carries the trace, not the driver) and keeps the
map on ``ctx.scopes``. A program without the scopes gives an empty map,
and every reader of a stage then returns nothing.
"""
from __future__ import annotations

import collections
import gc
import re
import sys

import devtrace

PREFIX = "paota."
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=")
OP_NAME = re.compile(r', metadata=\{[^}]*?op_name="([^"]*)"')


def scope_map(hlo_text: str) -> dict:
    """Instruction name -> its ``op_name`` metadata (empty where it has
    none), from compiled HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            op = OP_NAME.search(line, m.end())
            out[m.group(1)] = op.group(1) if op else ""
    return out


def _window_ops(trace):
    """Per chip, the (name, seconds) of each operation in the window,
    control-flow containers left out (as in ``devtrace.breakdown``)."""
    lo, hi = trace.window
    for ev in trace.devices.values():
        yield [(n, 1e-9 * (min(e, hi) - max(s, lo))) for n, s, e in ev
               if e > lo and s < hi
               and devtrace.SUFFIX.sub("", n) not in devtrace.CONTAINERS]


def scope_seconds(trace, smap: dict, scope) -> list:
    """Per chip, the device time in the window of the operations whose
    ``op_name`` contains ``scope`` (a nested scope matches its parent's
    name as a substring). ``scope=None``: the operations under no
    ``paota.`` scope, those ``smap`` cannot resolve included."""
    if scope is None:
        hit = lambda n: PREFIX not in smap.get(n, "")
    else:
        hit = lambda n: scope in smap.get(n, "")
    return [sum(s for n, s in ops if hit(n)) for ops in _window_ops(trace)]


def unresolved_seconds(trace, smap: dict) -> list:
    """Per chip, the device time in the window of operations that are not
    instructions of the compiled text."""
    return [sum(s for n, s in ops if n not in smap)
            for ops in _window_ops(trace)]


def unscoped_kinds(trace, smap: dict) -> list:
    """(kind, seconds) of the operations under no ``paota.`` scope in the
    window, by instruction name without its numeric suffix, summed and
    averaged over the chips, largest first."""
    totals = collections.Counter()
    chips = max(len(trace.devices), 1)
    for ops in _window_ops(trace):
        for n, s in ops:
            if PREFIX not in smap.get(n, ""):
                totals[devtrace.SUFFIX.sub("", n)] += s / chips
    return totals.most_common()


def host_span_seconds(trace, name: str) -> float:
    """Summed time of the host spans called ``name`` inside the window."""
    lo, hi = trace.window
    return 1e-9 * sum(min(e, hi) - max(s, lo) for n, s, e in trace.host
                      if n == name and e > lo and s < hi)


def live_driver():
    """The program's driver object of this process, where exactly one is
    alive (``ShardedPAOTA`` is a ``FusedPAOTA``)."""
    from repro.fl.fused import FusedPAOTA
    found = [o for o in gc.get_objects() if isinstance(o, FusedPAOTA)]
    return found[0] if len(found) == 1 else None


def stage_map(ctx) -> dict:
    """The scope map of the scan the window ran, built once per run and
    kept on ``ctx.scopes``; empty where the program names no stage. The
    compile is the one the warm-up made: the compile cache holds it."""
    if getattr(ctx, "scopes", None) is None:
        drv = live_driver()
        smap = {}
        if drv is not None:
            smap = scope_map(drv.compiled_scan_hlo(
                ctx.traffic["periods_per_advance"]))
        if not any(PREFIX in v for v in smap.values()):
            smap = {}
        ctx.scopes = smap
    return ctx.scopes


def stage_ms(ctx, names) -> float | None:
    """Device ms per period under any of the scopes ``names`` (``None``:
    under none), averaged over the chips; None where the program names no
    stage or the trace holds no device."""
    smap = stage_map(ctx)
    if not smap or not ctx.trace.devices:
        return None
    per_chip = [0.0] * len(ctx.trace.devices)
    for scope in names:
        for i, s in enumerate(scope_seconds(ctx.trace, smap, scope)):
            per_chip[i] += s
    return 1e3 * sum(per_chip) / len(per_chip) / ctx.periods


def note(msg: str):
    print(f"scopes: {msg}", file=sys.stderr, flush=True)
