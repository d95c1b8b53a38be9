"""Roofline share of the ``flash_attention`` kernel in the training step:
the causal attention's FLOPs and its q, k, v and output bytes, over the
kernel's device time."""


def read(r):
    return r.roofline("flash_attention", r.work["flash_flops_per_call"],
                      r.work["flash_bytes_per_call"])
