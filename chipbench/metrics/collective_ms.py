"""Time of the program's declared collectives per outer step: the device
time of the ops under a ``repro.comm.<name>`` scope (the scope
``core/comm.py`` puts around each declared collective, in every
executor) inside the traced window, over the outer steps, mean over the
chips.  Each chip's time is divided by the step modules its own profile
holds in the window, so a chip whose events end early in the profile
reads what its recorded steps took.

The note gives, in ms per outer step on the same terms, the time under
each scope (``repro.comm.<name>``, and ``repro.d3ca.map`` around D3CA's
primal-dual map, its psum included) and ``all_collectives``: every
collective op of the window by its HLO kind (as
``collective_exposed_ms`` finds them), those of the objectives outside
the step included.  A profile's op events hold no metadata, so an op's
scopes are read from the ``op_name`` metadata that the compiled programs
this process still holds give the op, matched by module and instruction
name.  A program without the scopes reads None.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, Tuple

from chipbench import trace_reduce as tr
from chipbench.metrics.collective_exposed_ms import COLLECTIVES
from chipbench.metrics.host_gap_ms import STEP_MODULES

#: an instruction of a module's HLO text with its op_name metadata
INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%([^\s=]+) = .*metadata=\{[^}]*op_name="([^"]*)"')
#: the scopes of the declared collectives, which make the value
COMM_SCOPE = "repro.comm."


def module_base(name: str) -> str:
    """``jit_step_fn(1234)``, a module as a profile names it, to
    ``jit_step_fn``, as its program names it."""
    return name.split("(", 1)[0]


def instruction(event: tr.Event) -> str:
    """A device op's HLO instruction name (``psum.12``)."""
    return event.name.split(" = ", 1)[0].lstrip("%")


def live_op_names(modules: Iterable[str]) -> Dict[Tuple[str, str], str]:
    """``(module, instruction) -> op_name`` over the compiled programs of
    this process named in ``modules``; an instruction whose programs of
    one name disagree on its op_name is left out."""
    import jax
    wanted = set(modules)
    found: Dict[Tuple[str, str], set] = {}
    for exe in jax.devices()[0].client.live_executables():
        for module in exe.hlo_modules():
            if module.name not in wanted:
                continue
            for line in module.to_string().splitlines():
                m = INSTRUCTION.match(line)
                if m:
                    found.setdefault((module.name, m.group(1)),
                                     set()).add(m.group(2))
    return {k: v.pop() for k, v in found.items() if len(v) == 1}


def scopes(op_name: str) -> Tuple[str, ...]:
    """The program's scopes on an op_name path
    (``jit(step_fn)/shard_map/repro.d3ca.map/repro.comm.w_contrib/psum``
    holds ``repro.d3ca.map`` and ``repro.comm.w_contrib``)."""
    return tuple(p for p in op_name.split("/")
                 if p.startswith((COMM_SCOPE, "repro.d3ca.")))


def enclosing(modules, event):
    """The module event on the same device that holds ``event``, or None."""
    i = bisect.bisect_right(modules, event.start, key=lambda m: m.start) - 1
    if i >= 0 and modules[i].end >= event.start:
        return modules[i]
    return None


def read(ctx):
    lo, hi = ctx.window
    chips = []      # (steps, [(module, instruction, ns)], collective ns)
    for dev in ctx.trace.devices[:ctx.chips]:
        steps = sum(tr.matches(m, STEP_MODULES)
                    for m in tr.within(dev.modules, lo, hi))
        if not steps:
            continue
        ops, coll_ns = [], 0.0
        for e in tr.within(dev.ops, lo, hi):
            if e.kind in tr.CONTAINER_KINDS:
                continue
            if tr.matches(e, COLLECTIVES):
                coll_ns += e.duration
            mod = enclosing(dev.modules, e)
            if mod is not None:
                ops.append((module_base(mod.name), instruction(e),
                            e.duration))
        chips.append((steps, ops, coll_ns))
    if not chips:
        return None
    names = live_op_names({mod for _, ops, _ in chips
                           for mod, _, _ in ops})
    comm = 0.0
    note: Dict[str, float] = {"all_collectives": 0.0}
    for steps, ops, coll_ns in chips:
        per_step = 1e-6 / steps / len(chips)
        note["all_collectives"] += coll_ns * per_step
        for mod, ins, ns in ops:
            found = scopes(names.get((mod, ins), ""))
            if any(s.startswith(COMM_SCOPE) for s in found):
                comm += ns * per_step
            for scope in found:
                note[scope] = note.get(scope, 0.0) + ns * per_step
    if comm <= 0:
        return None
    return {"value": comm, "note": dict(sorted(note.items()))}
