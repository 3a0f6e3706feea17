"""Tokens a held expert sees a decode step: the assignments computed
here over held experts x expert layers x decode steps, whole window
(the program's device-side counter ``moe_assignments_held``)."""


def read(ctx):
    c = ctx["counters"]
    slots = (c.get("experts_held") or 0) * (c.get("moe_layers") or 0) \
        * (c.get("decode_steps") or 0)
    if not slots or c.get("moe_assignments_held") is None:
        return None
    return c["moe_assignments_held"] / slots
