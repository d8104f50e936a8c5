"""Median time from due to first token, over the requests due in the
window (``window.first_token_s``)."""
import window as W


def read(run):
    return W.quantile_ms(W.first_token_s(run), 50)
