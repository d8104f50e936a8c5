"""Median first-token hold over the requests due in the window: from the
engine's ``Request.t_first_token`` (the first token read back on the host)
to the return of the ``step_block`` call that delivered it, which waits for
the call's fused decode block."""
import window as W


def read(run):
    return W.quantile_ms([t.t_first - t.handle.t_first_token
                          for t in W.due_in_window(run.load)
                          if t.t_first is not None and t.handle is not None
                          and t.handle.t_first_token is not None], 50)
