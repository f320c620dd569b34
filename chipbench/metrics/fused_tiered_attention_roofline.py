"""Share of its roofline that ``fused_tiered_attention`` reaches in the
traced window: the least time its calls need on this chip (operations and
bytes from ``chipbench/kernel_cost.py`` over each call's page counts, peaks
from ``peaks.json``) over the time its events took on the device.

Each traced step calls the kernel once per layer; the page counts are read
before the step. A trace whose kernel events do not number one per layer
and step cannot be matched to those counts and gives no value."""

from chipbench import kernel_cost

KERNEL = "fused_tiered_attention"


def read(run):
    tr, traced = run.trace, run.win.traced
    if not tr or not traced or not traced["metered"] or not tr["kernel_s"].get(KERNEL):
        return None
    a = run.arch
    calls = a.n_layers * len(traced["metered"])
    if tr["kernel_calls"][KERNEL] != calls:
        return None
    flops = nbytes = 0
    for warm, cold, host, rlen, _ in traced["metered"]:
        f, b = kernel_cost.fused_tiered_attention(
            warm, cold, host, rlen, page_tokens=int(run.cell.config["engine"]["page_tokens"]),
            kv_heads=a.n_kv_heads, head_dim=a.head_dim, heads=a.n_heads)
        flops += f
        nbytes += b
    least = kernel_cost.roofline_s(flops, nbytes, run.peaks)
    return 100.0 * least / tr["kernel_s"][KERNEL]
