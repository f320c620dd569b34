"""Whole decode step's share of the chip's bf16 peak in the traced window:
model operations per decoded token (``kernel_cost.decode_flops_per_token``
at each token's context length) summed over the tokens decoded in the
traced steps, over the traced seconds and the peak."""

from chipbench import kernel_cost


def read(run):
    tr, traced = run.trace, run.win.traced
    if not tr or not traced or not traced["metered"] or tr["window_s"] <= 0:
        return None
    flops = sum(kernel_cost.decode_flops_per_token(run.arch, float(c))
                for *_, ctx in traced["metered"] for c in ctx)
    return 100.0 * flops / tr["window_s"] / run.peaks["bf16_flops"]
