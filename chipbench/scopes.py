"""Device time by the program's named scopes.

The program names its layers with ``jax.named_scope``; the compiler keeps
each scope in the ``op_name`` metadata of every instruction traced under
it.  The TPU's device events, as ``jax.profiler.ProfileData`` gives them,
carry the instruction's text without that metadata (no ``tf_op`` stat),
so ``op_names_from_hlo`` maps each instruction name to its ``op_name``
from the compiled program's ``as_text()``, and ``op_name`` looks an event
up there.  The names are written here as literals and not imported from the
program, so a renamed scope reads as a metric gone missing, not as one
that followed it silently.

The rule, written once:

* an operation is classified by the innermost of ``SCOPES`` in its
  ``op_name`` (a transformation's wrapper, as in ``jvp(head_loss)`` or
  ``vmap(sara_sample)``, is taken off first);
* an operation of the model's scopes (``embed``, ``blocks``) whose name
  holds ``rematted_computation`` is recomputation, else one that holds
  ``transpose(`` is backward, else forward;
* only leaf operations count: events that contain no other event of
  their device.  A loop's time is then the union of its leaf operations'
  intervals, split by their own scopes, and not the loop's own span;
* a leaf of no scope takes the class of the innermost event around it
  that has one: XLA drops the metadata of some instructions it makes
  inside a loop, and the loop's own name keeps the scope.

A class's time is the union of its leaf operations' intervals inside the
window.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench import trace
from chipbench.trace import Event, Span

MODEL = ("embed", "blocks")
REFRESH = ("opt_refresh", "sketch", "power_iter", "qr", "small_svd",
           "sara_sample")
SCOPES = MODEL + ("head_loss", "opt_update") + REFRESH

LOOP_PREFIX = "repro.loop."
NO_LOOP_SPAN = "outside the loop's spans"

_WRAPPED = re.compile(r"(?:jvp|transpose|vmap)\((.*)\)")
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = ")
_METADATA = re.compile(r'metadata=\{op_name="([^"]*)"')


def op_names_from_hlo(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` of a compiled module's text."""
    out = {}
    for line in text.splitlines():
        m, meta = _INSTRUCTION.match(line), _METADATA.search(line)
        if m and meta:
            out[m.group(1)] = meta.group(1)
    return out


def op_name(event: Event, names: Dict[str, str]) -> Optional[str]:
    """The ``op_name`` the compiler kept for the event's instruction."""
    m = _INSTRUCTION.match(event.name)
    return names.get(m.group(1)) if m else None


def _bare(part: str) -> str:
    while True:
        m = _WRAPPED.fullmatch(part)
        if not m:
            return part
        part = m.group(1)


def scope_of(name: Optional[str]) -> Optional[str]:
    """The innermost of ``SCOPES`` in an ``op_name``."""
    for part in reversed((name or "").split("/")):
        bare = _bare(part)
        if bare in SCOPES:
            return bare
    return None


def classify(name: Optional[str]) -> Optional[str]:
    """The class an ``op_name`` falls in: "forward", "recompute" or
    "backward" for the model's scopes, any other scope by its own name;
    None for no scope."""
    scope = scope_of(name)
    if scope not in MODEL:
        return scope
    if "rematted_computation" in name:
        return "recompute"
    return "backward" if "transpose(" in name else "forward"


def _contains(outer: Event, inner: Event) -> bool:
    return inner.start < outer.end and inner.end <= outer.end


def _nested(events: Iterable[Event]) -> List[Tuple[Event, List[Event]]]:
    """Each leaf event (one that contains no other event of its device)
    with the events around it, innermost first."""
    by_plane: Dict[str, List[Event]] = defaultdict(list)
    for e in events:
        by_plane[e.plane].append(e)
    out = []
    for evs in by_plane.values():
        evs.sort(key=lambda e: (e.start, -e.end))
        stack: List[Event] = []
        for i, e in enumerate(evs):
            while stack and not _contains(stack[-1], e):
                stack.pop()
            nxt = evs[i + 1] if i + 1 < len(evs) else None
            if nxt is None or not _contains(e, nxt):
                out.append((e, stack[::-1]))
            stack.append(e)
    return out


def leaves(events: Iterable[Event]) -> List[Event]:
    """The events that contain no other event of their device."""
    return [e for e, _ in _nested(events)]


def _by_class(events: Sequence[Event], names: Dict[str, str]
              ) -> Dict[Optional[str], List[Event]]:
    out: Dict[Optional[str], List[Event]] = defaultdict(list)
    for e, around in _nested(events):
        cls = classify(op_name(e, names))
        for outer in around:  # a leaf of no scope takes its loop's
            if cls is not None:
                break
            cls = classify(op_name(outer, names))
        out[cls].append(e)
    return out


def _union_s(found: Iterable[Event], events: Sequence[Event], t0: float,
             t1: float) -> float:
    """Seconds of the union of ``found`` in [t0, t1], averaged over the
    devices of ``events``."""
    planes = {e.plane for e in events}
    return (sum(b - a for a, b in trace.intervals(found, t0, t1))
            / max(len(planes), 1))


def seconds(events: Sequence[Event], t0: float, t1: float,
            names: Dict[str, str]) -> Dict[Optional[str], float]:
    """Device seconds of each class (None: leaves of no scope) in [t0,
    t1]."""
    return {cls: _union_s(evs, events, t0, t1)
            for cls, evs in _by_class(events, names).items()}


def per_step(ctx, classes: Sequence[str]) -> Optional[float]:
    """A per-layer reading: device seconds per traced step of the union of
    ``classes``, with the traced programs' ``ctx.op_names``; None where no
    operation of them ran (a program without the scopes)."""
    by_class = _by_class(ctx.events, getattr(ctx, "op_names", {}))
    found = [e for cls in classes for e in by_class.get(cls, ())]
    if not found:
        return None
    return _union_s(found, ctx.events, ctx.t0, ctx.t1) / ctx.steps


def unscoped_share(events: Sequence[Event], t0: float, t1: float,
                   names: Dict[str, str]) -> float:
    """Share of the busy device time held by leaf operations of no
    scope."""
    busy = trace.busy(events, t0, t1)
    return (seconds(events, t0, t1, names).get(None, 0.0) / busy
            if busy else 0.0)


def loop_spans(path: str) -> List[Span]:
    """The program loop's host spans (``repro.loop.*``) of a trace file."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(LOOP_PREFIX):
                    start = ev.start_ns * 1e-9
                    spans.append(Span(ev.name, start,
                                      start + ev.duration_ns * 1e-9))
    return spans


def idle_gaps_program(events: Iterable[Event], spans: Iterable[Span],
                      t0: float, t1: float) -> List[Tuple[str, float]]:
    """``trace.idle_gaps`` named by the loop's spans: each gap by the
    ``repro.loop.*`` span that overlaps it most (of two alike, the inner
    one)."""
    loop = [s for s in spans if s.name.startswith(LOOP_PREFIX)]
    return [(NO_LOOP_SPAN if name == trace.NO_SPAN else name, s)
            for name, s in trace.idle_gaps(events, loop, t0, t1)]
