"""Share of the traced window in which no operation runs on the chip,
averaged over the chips."""

from chipbench import trace as T


def read(run):
    share = run.trace and T.idle_share(run.trace, *run.trace_window)
    return None if share is None else 100.0 * share
