"""Roofline share of the LM bucket step: the least time one call could
take on this chip, max(operations / peak, bytes / HBM bandwidth), both
reckoned from shapes (the kind's ``flops_per_token`` and
``update_bytes``), over the call's device time in the trace (``XLA
Modules`` events of ``jit_step``). ``note`` says which bound applies."""
from bench import roofline


def _work(ctx):
    """(operations, bytes) of one call: every slot's update."""
    cell, f = ctx["cell"], ctx["flops"]
    tr = cell.traffic
    slots = int(tr["slots_per_chip"])
    tokens = int(tr["batch"]) * int(tr["seq"])
    return (slots * tokens * f.flops_per_token(cell.config, int(tr["seq"])),
            slots * f.update_bytes(cell.config))


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["step_calls"]:
        return None
    return roofline.share(*_work(ctx), ctx["peaks"],
                          t["step_s"] / t["step_calls"])


def note(ctx):
    return roofline.note(*_work(ctx), ctx["peaks"])
