"""Model FLOP/s utilization of the LM updates: training operations per
token (the kind's ``flops_per_token``: for ``flops/lm.py`` 6 per matmul
parameter plus causal attention, nothing recomputed) times the tokens
per second of the traced interval, over chips times the bf16 peak."""


def read(ctx):
    cell = ctx["cell"]
    if ctx["trace"] is None:
        return None
    per_token = ctx["flops"].flops_per_token(cell.config,
                                             int(cell.traffic["seq"]))
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * per_token * ctx["trace_work_per_s"] / peak
