"""95th percentile admission wait over the requests due in the window: the
engine's ``Request.t_admitted`` (stamped just before its group's admission
call, on the harness's clock) less its ``arrival_time`` (when it was due)."""
import window as W


def read(run):
    return W.quantile_ms([t.handle.t_admitted - t.handle.arrival_time
                          for t in W.due_in_window(run.load)
                          if t.handle is not None
                          and t.handle.t_admitted is not None], 95)
