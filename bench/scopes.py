"""Device time by layer of the train step, from the program's named
scopes.

The train step opens one ``jax.named_scope`` per layer (``LAYERS``,
the program's ``train_step.LAYER_SCOPES``).  A scope reaches each
instruction of the compiled step as a ``/``-separated segment of its
``op_name`` metadata; a fusion carries the ``op_name`` of its root.  A
device operation of the trace is named by its HLO instruction, the
first word of its label (``tracereduce.label``).  So:

- ``parse(hlo_text)`` reads the compiled step's text
  (``compiled.as_text()``) into each instruction's layer, the first of
  ``LAYERS`` among its ``op_name``'s segments (None where no segment is
  one), and the computations each instruction calls.  An instruction
  with no ``op_name`` at all is one the compiler made: a layout copy,
  the parameters' cast to the compute type, the ``dynamic-update-slice``
  chain a concatenation became.  It takes the layer of what it is made
  of: a fusion the one its fused instructions share, else the one its
  operands share, else the one its users share, else None (repeated
  until nothing changes);
- ``layer_seconds(op_seconds, layer, nested)`` sums the trace's device
  seconds by layer, None for the unscoped.

A ``while`` or ``conditional`` in the trace spans the operations of its
body, which the trace lists as well.  Each interval is counted once, in
the innermost operation: a container keeps only its own seconds less
those of the operations nested in it (``Module.nested``).
"""
from __future__ import annotations

import dataclasses
import re

LAYERS = ("fwd_bwd", "ravel", "level_update", "encode", "collective",
          "decode", "optimizer", "step_metrics")

_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([^\s(%]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([^\s,(){}]+)")
_CALL = re.compile(r"\b(?:body|condition|to_apply|calls|true_computation|"
                   r"false_computation)=%?([^\s,{}]+)")
_CALL_LIST = re.compile(r"\b(?:branch_computations|called_computations)="
                        r"\{([^}]*)\}")


def layer_of(op_name: str):
    """The first of ``LAYERS`` among the ``/``-separated segments of
    ``op_name``, or None."""
    for seg in op_name.split("/"):
        if seg in LAYERS:
            return seg
    return None


@dataclasses.dataclass
class Module:
    """``layer``: instruction -> layer or None; ``calls``: instruction
    -> the computations it calls; ``body``: computation -> its
    instructions."""

    layer: dict
    calls: dict
    body: dict

    def scoped(self) -> bool:
        """Whether any instruction carries a layer scope (a program
        without the scopes carries none)."""
        return any(v is not None for v in self.layer.values())

    def nested(self, present) -> dict:
        """{instruction: the instructions of ``present`` nested directly
        in it}: those reached through the computations it calls without
        passing through another instruction of ``present``."""
        present = set(present)
        out = {}
        for name in present:
            found, seen = [], set()
            todo = list(self.calls.get(name, ()))
            while todo:
                comp = todo.pop()
                if comp in seen:
                    continue
                seen.add(comp)
                for instr in self.body.get(comp, ()):
                    if instr in present:
                        found.append(instr)
                    else:
                        todo.extend(self.calls.get(instr, ()))
            if found:
                out[name] = sorted(found)
        return out


def _inherit(layer: dict, operands: dict, fused: dict,
             orphans: list) -> None:
    """Give each of ``orphans`` (instructions with no ``op_name``) the
    layer of what it is made of: the one its ``fused`` instructions
    share, else the one its operands share.  Only where neither gives
    one, the one its users share.  Each round reads the last round's
    layers, so the order of the instructions does not matter."""
    users = {}
    for name, ops in operands.items():
        for op in ops:
            users.setdefault(op, []).append(name)

    def shared(names):
        found = {layer[n] for n in names if layer.get(n) is not None}
        return found.pop() if len(found) == 1 else None

    def made_of(name):
        return shared(fused.get(name, ())) or shared(operands[name])

    todo = set(orphans)
    for rule in (made_of, lambda n: shared(users.get(n, ())), made_of):
        while True:
            got = {n: rule(n) for n in todo}
            got = {n: v for n, v in got.items() if v is not None}
            if not got:
                break
            layer.update(got)
            todo -= set(got)


def parse(text: str) -> Module:
    layer, calls, body = {}, {}, {}
    refs, fusions, orphans = {}, set(), []
    comp = None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _HEAD.match(line)
            comp = m.group(1) if m else None
            if comp is not None:
                body[comp] = []
            continue
        m = _INSTR.match(line) if comp is not None else None
        if m is None:
            continue
        name = m.group(1)
        body[comp].append(name)
        op = _OP_NAME.search(line)
        layer[name] = layer_of(op.group(1)) if op else None
        if op is None:
            orphans.append(name)
        rest = line[m.end():]
        refs[name] = (comp, _REF.findall(rest))
        if " fusion(" in rest:
            fusions.add(name)
        called = _CALL.findall(line)
        for group in _CALL_LIST.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",")
                       if c.strip()]
        if called:
            calls[name] = tuple(called)
    # operands: the names an instruction's line holds of instructions of
    # its own computation (called computations are not instructions)
    members = {c: set(v) for c, v in body.items()}
    operands = {n: [r for r in rs if r in members[c] and r != n]
                for n, (c, rs) in refs.items()}
    fused = {n: body.get(calls[n][0], ()) for n in fusions if n in calls}
    _inherit(layer, operands, fused, orphans)
    return Module(layer, calls, body)


def op_seconds_by_name(summary: dict) -> dict:
    """The trace summary's seconds per operation, keyed on the HLO
    instruction's name (the first word of each label)."""
    out = {}
    for label, s in summary["op_seconds"].items():
        name = label.split(" ")[0]
        out[name] = out.get(name, 0.0) + s
    return out


def layer_seconds(op_seconds: dict, layer: dict, nested: dict) -> dict:
    """{layer or None: device seconds}, each operation's seconds less
    those of the operations ``nested`` in it (never below 0)."""
    out = {}
    for name, s in op_seconds.items():
        own = s - sum(op_seconds[c] for c in nested.get(name, ()))
        key = layer.get(name)
        out[key] = out.get(key, 0.0) + max(own, 0.0)
    return out


def per_chip(run):
    """{layer or None: device seconds per chip over the traced window}
    of ``run``, or None for an untraced run or a program without the
    scopes.  (Parsing the step's text takes a tenth of a second.)"""
    if run.trace is None:
        return None
    module = parse(run.compiled.as_text())
    if not module.scoped():
        return None
    ops = op_seconds_by_name(run.trace)
    seconds = layer_seconds(ops, module.layer, module.nested(ops))
    return {k: v / run.chips for k, v in seconds.items()}


def step_ms(run, name: str):
    """Device milliseconds per step of layer ``name`` in the traced
    window, or None."""
    seconds = per_chip(run)
    if seconds is None or not run.steps:
        return None
    return 1000.0 * seconds.get(name, 0.0) / run.steps
