"""The decode MoE expert kernel's share of its roofline, in %.

Kernel time: the device time of the ``gather_swiglu`` ops in the trace.
Least time: for every decode step the traced ``step_block`` calls ran and
every layer, the larger of FLOPs over peak and bytes over bandwidth for
the work the algorithm needs: the tables of the expected DISTINCT experts
the step's active tokens x top-k draws hit in that layer, plus the rows in
and out (``counts.moe_call``). Withheld where the trace holds another
number of kernel ops than steps x layers (it lost device events)."""
import counts as C
import window as W

KERNEL = "gather_swiglu"


def read(run):
    w = run.widths
    need, calls = 0.0, 0
    for b in W.traced_blocks(run):
        if b.decode_steps:
            tokens = b.decode_tokens / b.decode_steps
            need += b.decode_steps * sum(
                C.least_time(*C.moe_call(w, live, tokens), run.peaks)
                for live in w["live"])
            calls += b.decode_steps * w["L"]
    return W.kernel_share(run, KERNEL, need, calls)
