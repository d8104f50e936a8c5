"""Model FLOPs of the tokens the window processed (prompts prefilled and
tokens decoded, from shapes: ``counts.py``) over window x chips x the
chip's bf16 peak, in %."""
import counts as C
import window as W


def read(run):
    w = run.widths
    flops = sum(sum(C.prefill_flops(w, p) for p in b.admitted)
                + C.decode_flops(w, b.decode_tokens, b.decode_ctx)
                for b in W.blocks_in_window(run))
    return 100.0 * flops / (W.window_s(run) * run.chips
                            * run.peaks["bf16_flops"])
