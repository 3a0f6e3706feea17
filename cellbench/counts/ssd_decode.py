"""Operations and bytes the Mamba-2 decode kernel (``apex_ssd_decode``)
needs for the decode steps of the traced stretch.  One state update (one
active slot in one layer) reads the slot's ``heads x head size x state
size`` float32 state and writes it back, and spends about 6 operations
an element of it (the decay, the rank-one update's product and sum, the
product with ``C`` and its sum).  The program counts the updates on the
device over the whole window (``ssm_state_updates``); the traced
stretch's share is its share of the window's decode steps."""


def state_elements(model):
    """A sequence's state of one layer, in elements; None for a model
    without one."""
    keys = ("mamba_n_heads", "mamba_d_head", "mamba_d_state")
    if any(k not in model for k in keys):
        return None
    return model["mamba_n_heads"] * model["mamba_d_head"] \
        * model["mamba_d_state"]


def total(ctx):
    c, state = ctx["counters"], state_elements(ctx["model"])
    if state is None or not c.get("decode_steps") \
            or not c.get("traced_steps") \
            or c.get("ssm_state_updates") is None:
        return None
    updates = c["ssm_state_updates"] * c["traced_steps"] / c["decode_steps"]
    return {"flops": 6.0 * state * updates,
            "bytes": 2.0 * 4 * state * updates}
