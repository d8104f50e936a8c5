"""The prefill MoE expert kernel's share of its roofline, in %.

Kernel time: the device time of the ``grouped_swiglu`` ops in the trace.
Least time: for every admission group the traced ``step_block`` calls
prefilled (one per prompt pad length) and every layer, the larger of FLOPs
over peak and bytes over bandwidth for the group's REAL prompt tokens
(padding excluded): the expected distinct experts' tables plus the rows in
and out (``counts.moe_call``). Withheld where the trace holds another
number of kernel ops than groups x layers (it lost device events)."""
import counts as C
import window as W

KERNEL = "grouped_swiglu"


def read(run):
    w = run.widths
    need, calls = 0.0, 0
    for b in W.traced_blocks(run):
        groups = {}
        for n, pad in zip(b.admitted, b.buckets):
            groups[pad] = groups.get(pad, 0) + n
        for tokens in groups.values():
            need += sum(C.least_time(*C.moe_call(w, live, tokens), run.peaks)
                        for live in w["live"])
            calls += w["L"]
    return W.kernel_share(run, KERNEL, need, calls)
