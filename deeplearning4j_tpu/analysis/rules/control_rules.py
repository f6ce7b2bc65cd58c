"""CTL rules: keep fleet/serving actuation inside the control plane.

PR 16's closed loop works precisely because every automated actuator
invocation funnels through one auditable seam: a
:class:`~deeplearning4j_tpu.control.plane.ControlPolicy` action, edge-
triggered, cooldown-latched, recorded as a ``control_action`` flight
event. An actuator call sprinkled anywhere else — a training script
that quietly ``scale_to``\\ s its own fleet, a handler that mutates a
model's admission cap inline — is an automated action no operator can
see on ``GET /control``, no cooldown ever latches, and no flight event
reconstructs. CTL001 fences those call sites.
"""
from __future__ import annotations

import ast
from typing import Iterator

from . import Rule, register

#: the actuator surface the control plane owns: fleet membership
#: (scale_to/remap/restart) and serving admission mutation
_ACTUATORS = {"scale_to", "remap", "restart", "set_admission"}


@register
class ActuatorOutsideControlPlane(Rule):
    id = "CTL001"
    title = "fleet/serving actuator call outside the control plane"
    rationale = (
        "scale_to/remap/restart/set_admission are the actuators the "
        "closed-loop control plane (control/) owns: invoked there, every "
        "action is edge-triggered, hysteresis/cooldown-latched against "
        "flapping, counted in control_actions_total, and recorded as a "
        "control_action flight event carrying the triggering alert's "
        "rule and exemplar trace — the whole incident reconstructs from "
        "GET /events. The same call anywhere else is an invisible "
        "mutation of fleet membership or serving admission: no operator "
        "surface shows it, no cooldown bounds it, and a flapping caller "
        "can shred the fleet. Route automated actions through a "
        "ControlPolicy; manual/runbook invocations belong in the "
        "paramserver package itself or tests (both exempt, as are self.* "
        "forwards — the definition pattern, e.g. "
        "ServedModel.set_admission delegating to its own batcher).")

    def check(self, tree, lines, path) -> Iterator:
        p = path.replace("\\", "/")
        parts = p.split("/")
        if "tests" in parts or "control" in parts or "paramserver" in parts:
            return
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute) \
                    or f.attr not in _ACTUATORS:
                continue
            # self.X(...) / self.attr.X(...): a class forwarding to its
            # own component defines the actuator, it does not actuate
            base = f.value
            while isinstance(base, (ast.Attribute, ast.Subscript)):
                base = base.value
            if isinstance(base, ast.Name) and base.id == "self":
                continue
            yield self.finding(
                node, lines, p,
                f"actuator call .{f.attr}(...) outside the control "
                f"plane — route automated fleet/serving actions through "
                f"a ControlPolicy (control/) so they are cooldown-"
                f"latched, counted, and flight-recorded")
