"""95th percentile queue wait over the requests due in the window: from
due to the start of the ``step_block`` call that delivered the first
token (``window.queue_wait_s``)."""
import window as W


def read(run):
    return W.quantile_ms(W.queue_wait_s(run), 95)
