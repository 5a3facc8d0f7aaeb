"""The FedFog round's phases on the device, from the program's own map.

The compiled round tags each HLO instruction with the ``fedfog.*`` scope
it was traced under (``fl/round.py``), and ``launch/train.py`` registers
that map with ``repro.obs.spans`` as it builds the round. A device
operation of the trace belongs to a phase when its instruction's name is
in the map and its event name prints the same result type, opcode and
operand names as the compiled text does (``heads``): another program run
in the window, such as the feed's, can reuse an instruction name, and
even its type (a ``pad_clamp_fusion`` of the feed did on the chip), but
not with the same operands. A phase's time is the union of its
operations' intervals, so a ``while`` and the body it runs count once.

A program that registers nothing (one without ``repro.obs.spans``)
leaves these metrics out; one that registers an empty map is an error.
"""
from __future__ import annotations

import re

import numpy as np

import xplane as trace

_HEAD_RE = re.compile(r"^(.*?)\s+([\w\-]+)\(")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _spans():
    """The program's span recorder, or None where the program has none."""
    try:
        from repro.obs import spans
    except ImportError:
        return None
    return spans


def round_program():
    """The last round program the process built, as ``repro.obs.spans``
    holds it; None where the program registers none."""
    spans = _spans()
    progs = spans.programs() if spans is not None else []
    if not progs:
        return None
    prog = progs[-1]
    if not prog["phases"]:
        raise SystemExit(
            f"bench: round program {prog['module']!r} maps no instruction "
            "to a fedfog.* scope: its executable came from a compile-cache "
            "entry keyed without metadata; clear .jax_cache_bench")
    return prog


def signature(event: str):
    """``"TYPE opcode(operand,...)"`` of a device event's name, which
    prints the instruction with typed operands; None where the name is
    cut short before its operands close."""
    rest = event.split(" = ", 1)[1] if " = " in event else ""
    m = _HEAD_RE.match(rest)
    if not m:
        return None
    depth, i = 1, m.end()
    while depth and i < len(rest):
        depth += (rest[i] in "([{") - (rest[i] in ")]}")
        i += 1
    if depth:
        return None
    names = _OPERAND_RE.findall(rest[m.end():i - 1])
    return f"{m.group(1)} {m.group(2)}({','.join(names)})"


def matcher(prog: dict, scope: str):
    """``name -> bool``: the device event is an instruction of ``prog``
    traced under ``scope``."""
    phases, heads = prog["phases"], prog["heads"]

    def match(name: str) -> bool:
        head = trace.op_head(name)
        if phases.get(head) != scope or " = " not in name:
            return False
        want, sig = heads[head], signature(name)
        if sig is not None:
            return sig == want
        # Cut short: what is printed must agree up to the operands.
        rest, upto = name.split(" = ", 1)[1], want[:want.rindex("(") + 1]
        return rest.startswith(upto) or upto.startswith(rest)

    return match


def phase_seconds(tr: trace.Trace, prog: dict, scope: str):
    """Union device time, averaged over devices, of ``prog``'s operations
    under ``scope`` inside the window; None when the trace holds no device
    operations at all (no accelerator). A phase that matched nothing on a
    device is an error."""
    if not any(tr.ops):
        return None
    lo, hi = tr.window()
    match = matcher(prog, scope)
    t = float(np.mean([
        trace.union_length([(s, e) for n, s, e in ops if match(n)], lo, hi)
        for ops in tr.ops]))
    if t <= 0:
        raise SystemExit(f"bench: no device operation in the window matched "
                         f"{scope} of round program {prog['module']!r}")
    return t


def per_round_ms(ctx: dict, scope: str):
    """A phase's device time per window round, in milliseconds."""
    prog = round_program()
    if prog is None:
        return None
    t = phase_seconds(ctx["trace"], prog, scope)
    if t is None:
        return None
    return 1e3 * t / ctx["work"]["rounds"]


def setup_seconds(*names: str):
    """Summed totals of the program's set-up spans ``names``; None where
    the program records none of them."""
    spans = _spans()
    if spans is None:
        return None
    st = spans.stats()
    if not any(n in st for n in names):
        return None
    return sum(st[n].total_s for n in names if n in st)
