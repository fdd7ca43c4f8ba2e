"""Named phases of the step program, and the map from a compiled program's
instructions back to them.

The step (core/step.py) runs its phases in order under `jax.named_scope`s
named in `STEP_PHASES`. A scope changes only the ops' `op_name` metadata:
the optimized program is the same instruction for instruction, so
trajectories do not move. `op_scopes` reads the metadata back out of a
compiled program's text (`compiled.as_text()`), so a device profile's
ops, which carry only instruction names such as `%fusion.764`, can be
summed by phase (`Runtime.fused_op_scopes`).
"""

from __future__ import annotations

import contextlib
import re

import jax

# in the order the step runs them; the plane folds and the ring compile
# only where their SimConfig field turns them on
STEP_PHASES = (
    "step.pick",        # 1. earliest eligible event, tie-break, pop
    "step.supervisor",  # 2. supervisor op, Lamport clock, span accumulation
    "step.handler",     # 3. protocol handler, gray-failure reads, cancels
    "step.emit",        # 4. emissions into the event table, stat counters
    "step.profile",     # cfg.profile counter plane
    "step.latency",     # cfg.latency_hist plane
    "step.span",        # cfg.span_attr plane
    "step.sketch",      # cfg.sketch_slots prefix sketch
    "step.series",      # cfg.series_windows plane
    "step.check",       # 5. end conditions, invariant, halt_when
    "step.ring",        # cfg.trace_cap flight recorder
)

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+)\s+=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_PHASE = re.compile(r"step\.[A-Za-z_]+")


class Phases:
    """The step's phases as `jax.named_scope`s, one open at a time:
    `to(name)` closes the phase in progress and opens `name`; leaving the
    `with` block closes the last one."""

    def __enter__(self):
        self._open = contextlib.ExitStack()
        return self

    def to(self, name: str) -> None:
        if name not in STEP_PHASES:
            raise ValueError(f"unknown step phase {name!r}")
        self._open.close()
        self._open.enter_context(jax.named_scope(name))

    def __exit__(self, *exc):
        self._open.close()


def scope_of(op_name: str) -> str:
    """The innermost `step.*` phase in an `op_name` path, or "" for
    none."""
    found = [p for p in _PHASE.findall(op_name) if p in STEP_PHASES]
    return found[-1] if found else ""


def op_scopes(hlo_text: str) -> dict[str, str]:
    """{instruction name (`%fusion.764`): its innermost `step.*` phase,
    or "" for none} over every instruction of an HLO module's text. A
    fusion carries its root's metadata, so it counts under that root's
    phase; an instruction without metadata belongs to no phase."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is not None:
            op = _OP_NAME.search(line)
            out[m.group(1)] = scope_of(op.group(1)) if op else ""
    return out
