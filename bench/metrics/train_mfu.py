"""The training step's share of the chip's bf16 peak: forward and backward
FLOPs per token that the model requires (no recompute, none of the reference
VJP's extra work) times the tokens per second of the traced window."""


def read(r):
    return r.mfu(r.work["train_flops_per_token"])
