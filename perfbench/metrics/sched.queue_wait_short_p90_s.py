"""90th percentile of a short request's wait from due time to its dispatch
(``ServingEngine.dispatch_log``); one not dispatched at the close counts at
its wait so far."""

from perfbench.stats import percentile, queue_waits


def read(run):
    return percentile(queue_waits(run.window.sent, run.window.close, "short"),
                      90)
