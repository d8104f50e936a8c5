"""Prompt tokens prefilled plus tokens generated by the calls that returned
in the window, over the window."""
import window as W


def read(run):
    bl = W.blocks_in_window(run)
    return sum(b.tokens_out + sum(b.admitted) for b in bl) / W.window_s(run)
