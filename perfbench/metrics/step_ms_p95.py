"""95th percentile of the host-clock time of every step in the window,
in milliseconds. A step runs from the loop's fetch of its batch to the
next fetch, after the step's ``block_until_ready``."""
import statistics


def read(ctx):
    if ctx.trace is not None or len(ctx.step_s) < 20:
        return None
    return 1e3 * statistics.quantiles(ctx.step_s, n=100,
                                      method="inclusive")[94]
