"""Per step: milliseconds in which a collective was in flight on a
device while no other op ran on it (mean over devices)."""
from chipbench import xplane


def read(run):
    if run.trace is None or not run.traced.get("steps") or run.chips < 2:
        return None
    exposed = xplane.exposed_collective_by_device(run.trace)
    if not exposed:
        return None
    return 1e3 * sum(exposed.values()) / len(exposed) / run.traced["steps"]
