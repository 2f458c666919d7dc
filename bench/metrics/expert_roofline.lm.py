"""Roofline share of the held experts' grouped matmuls: the least time a
step's expert products, forward and backward, could take on this chip,
max(operations / peak, bytes / HBM bandwidth), both reckoned from shapes
at a balanced load (the kind's ``expert_work``), over the device time a
step of the ops named after the grouped-matmul kernels (the Pallas
megablox ``gmm`` and ``tgmm``, forward, recomputed forward and both parts
of the backward): their total inside the step calls of the traced window
over those calls. Nothing where no such op ran (on the CPU, or in a model
with no expert layer). ``note`` says which bound applies."""
from bench import roofline, trace

KERNELS = ("gmm",)


def _work(ctx):
    """(operations, bytes) of one step: every slot's sequences."""
    cell = ctx["cell"]
    tr = cell.traffic
    return ctx["flops"].expert_work(
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
