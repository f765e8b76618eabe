"""Device time of a program's operations by the named scope they were
traced under (``jax.named_scope``), in a traced run.

A device op event names its HLO instruction (on a TPU the whole
instruction, ``%fusion.3 = f32[8,128]{...} fusion(...)``), not the name
stack it was traced under; the profile's reader (``jax.profiler.
ProfileData``) gives an event's own stats, not its metadata's.  The name
stack is the instruction's metadata in the compiled program
(``metadata={op_name="jit(decode)/.../moe.experts/dot_general" ...}``), so
the family gives, per program, each instruction's path (``op_paths``, from
the program's compiled text through ``instruction_paths``).  An op belongs
to a scope when the scope is one whole component of its instruction's path.
XLA fuses ops across scopes; a fusion carries its root's metadata, so the
split is by the op that finishes each fusion.  An instruction that XLA makes
itself carries no name stack (on a TPU the ``ragged-dot`` kernel of
``jax.lax.ragged_dot``, named ``op_name="ragged-dot-none"``, and the
weight prefetches, with no ``op_name``): it takes the path of the nearest
instruction that consumes its result and has one.  All times are seconds.
"""
from __future__ import annotations

import bisect
import re
from collections import deque
from typing import Dict, List, Optional, Tuple

from perfbench import xtrace

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
#: least share of the device time inside a program's runs whose op names
#: an instruction of the compiled text; below it the text is not the
#: program that ran
MATCHED = 0.9


def instruction_paths(hlo_text: str) -> Dict[str, str]:
    """Each instruction of a compiled HLO text with a path: {instruction
    name: name stack}.  An instruction whose ``op_name`` is no name stack
    (holds no ``/``), or that has none, takes the stack of the nearest
    instruction, breadth first over the users of its result, that has one;
    failing that it keeps its own ``op_name``, if any."""
    own: Dict[str, Optional[str]] = {}
    users: Dict[str, List[str]] = {}
    for name, rhs in _INSTRUCTION.findall(hlo_text):
        m = _OP_NAME.search(rhs)
        own[name] = m.group(1) if m else None
        for ref in set(_REF.findall(rhs)):
            users.setdefault(ref, []).append(name)
    paths = {}
    for name, path in own.items():
        if path is None or "/" not in path:
            seen, queue = {name}, deque(users.get(name, ()))
            while queue:
                u = queue.popleft()
                if u in seen:
                    continue
                seen.add(u)
                if own.get(u) and "/" in own[u]:
                    path = own[u]
                    break
                queue.extend(users.get(u, ()))
        if path is not None:
            paths[name] = path
    return paths


def scoped_seconds(ctx, scope: str, program: str
                   ) -> Tuple[Optional[float], int]:
    """Mean over devices of the device time of ops under ``scope`` inside
    the runs of ``program`` that start in the traced window, and the number
    of runs (first device); None for the time where the family gives no
    instruction paths for the program or the window holds no run of it.
    Raises where the ops' names miss the compiled text, or no op of the
    runs carries the scope."""
    paths = getattr(ctx.family, "op_paths", lambda _: None)(program)
    if paths is None:
        return None, 0
    pat = re.compile(rf"(^|/){re.escape(scope)}(/|$)")
    per_dev, runs, found, inside, named = [], None, False, 0.0, 0.0
    for dev in ctx.trace.devices:
        spans = [(m.start, m.end) for m in dev.program_runs(program)
                 if ctx.lo <= m.start < ctx.hi]
        runs = len(spans) if runs is None else runs
        starts = [a for a, _ in spans]
        tot = 0.0
        for e in dev.ops:
            i = bisect.bisect_right(starts, e.start) - 1
            if i < 0 or e.start >= spans[i][1]:
                continue
            path = paths.get(xtrace.op_label(e.name).split(" ", 1)[0])
            inside += e.dur
            if path is None:
                continue
            named += e.dur
            if pat.search(path):
                tot += e.dur
                found = True
        per_dev.append(tot)
    if not runs:
        return None, 0
    if named < MATCHED * inside:
        raise RuntimeError(
            f"{named / inside:.1%} of the op time in the {program} runs "
            "names an instruction of its compiled text: the text is not the "
            "program that ran")
    if not found:
        raise RuntimeError(f"{runs} {program} runs in the traced window, but "
                           f"no op of them carries the scope {scope!r}")
    return sum(per_dev) / len(per_dev), runs
