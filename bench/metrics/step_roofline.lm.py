"""Roofline share of the LM bucket step: the least time one call could
take on this chip, max(operations / peak, bytes / HBM bandwidth), both
reckoned from shapes (``flops/lm.py``), over the call's device time in
the trace (``XLA Modules`` events of ``jit_step``). ``note`` says which
bound applies."""


def _bounds(ctx):
    cell, f, pk = ctx["cell"], ctx["flops"], ctx["peaks"]
    tr = cell.traffic
    slots = int(tr["slots_per_chip"])
    tokens = int(tr["batch"]) * int(tr["seq"])
    ops = slots * tokens * f.flops_per_token(cell.config, int(tr["seq"]))
    moved = slots * f.update_bytes(cell.config)
    return ops / pk["bf16_flops_per_s"], moved / pk["hbm_bytes_per_s"]


def read(ctx):
    t = ctx["trace"]
    if t is None or ctx["cell"].kind != "lm" or not t["step_calls"]:
        return None
    per_call = t["step_s"] / t["step_calls"]
    return 100.0 * max(_bounds(ctx)) / per_call


def note(ctx):
    compute, memory = _bounds(ctx)
    return (f"compute-bound ({compute * 1e3:.1f} ms of operations, "
            f"{memory * 1e3:.1f} ms of bytes)" if compute >= memory else
            f"memory-bound ({memory * 1e3:.1f} ms of bytes, "
            f"{compute * 1e3:.1f} ms of operations)")
