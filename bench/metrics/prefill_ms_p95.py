"""95th percentile prefill time over the requests due in the window: the
engine's ``Request.t_first_token`` less its ``t_admitted``, from its
group's admission call to the first token read back on the host, device
time included."""
import window as W


def read(run):
    return W.quantile_ms([t.handle.t_first_token - t.handle.t_admitted
                          for t in W.due_in_window(run.load)
                          if t.handle is not None
                          and t.handle.t_first_token is not None], 95)
