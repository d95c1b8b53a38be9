"""The forward step's share of the chips' bf16 peak: router and routed-expert
FLOPs per token times the tokens per second of the traced window."""


def read(r):
    return r.mfu(r.work["fwd_flops_per_token"])
