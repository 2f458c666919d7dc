"""Roofline share of the step's training attention kernels: the least
time a step's causal attention, forward and backward, could take on this
chip, max(operations / peak, bytes / HBM bandwidth), both reckoned from
shapes (the kind's ``attention_work``), over the device time a step of
the ops named after the three Pallas flash kernels (forward, dK/dV, dQ):
their total inside the step calls of the traced window over those
calls. Nothing where no such op ran (on the CPU, or on the chunked
path). ``note`` says which bound applies."""
from bench import roofline, trace

KERNELS = ("flash_attention", "flash_mha_bwd_dkv", "flash_mha_bwd_dq")


def _work(ctx):
    """(operations, bytes) of one step: every slot's sequences."""
    cell = ctx["cell"]
    tr = cell.traffic
    return ctx["flops"].attention_work(
        cell.config, int(tr["slots_per_chip"]) * int(tr["batch"]),
        int(tr["seq"]))


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["step_calls"]:
        return None
    kernels = trace.kernel_s(t["step_ops"], KERNELS)
    if kernels is None:
        return None
    return roofline.share(*_work(ctx), ctx["peaks"],
                          kernels / t["step_calls"])


def note(ctx):
    return roofline.note(*_work(ctx), ctx["peaks"])
