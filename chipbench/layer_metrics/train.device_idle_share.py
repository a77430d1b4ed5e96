"""Share of the traced window in which no op ran on the device (the
fullest-loaded device where there are several)."""
from chipbench import xplane


def read(run):
    share = xplane.idle_share(run.trace) if run.trace is not None else None
    return None if share is None else 100.0 * share
