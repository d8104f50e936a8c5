"""95th percentile inter-token latency over every token delivered in the
window after its request's first call (``window.token_gaps``): a token
waits for the whole call that makes it, admissions in it too."""
import window as W


def read(run):
    return W.quantile_ms(W.token_gaps(run), 95)
