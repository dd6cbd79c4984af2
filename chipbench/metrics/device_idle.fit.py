"""Device idle share over the traced window: 1 - the union of the
device's operation intervals over the window, mean over the devices."""
import sys

from chipbench import trace


def read(run):
    if run.trace is None:
        return None
    mean, shares = trace.idle_pct(run.trace)
    for dev, pct in sorted(shares.items()):
        print(f"[{run.cell.name}] idle {dev}: {pct!r} % on {run.on}",
              file=sys.stderr)
    return mean
