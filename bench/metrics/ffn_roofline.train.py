"""Roofline share of the ``grouped_ffn`` kernel in the training step, whose
forward alone runs the kernel: FLOPs of the valid rows and bytes of the held
weights read once plus the rows in and out, over the kernel's device time."""


def read(r):
    return r.roofline("grouped_ffn", r.work["ffn_flops_per_call"],
                      r.work["ffn_bytes_per_call"])
