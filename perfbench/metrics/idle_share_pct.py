"""idle_share_pct: share of the traced call in which no operation ran on
the device, % (layer: device).

1 - busy / window per device, busy being the union of the device's
operation intervals within the host span of the call, averaged over the
devices used.  Moves `sim_years_per_s`."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    shares = [1.0 - d.busy_ns / (d.window[1] - d.window[0])
              for d in run.trace.devices if d.window[1] > d.window[0]]
    return 100.0 * sum(shares) / len(shares) if shares else None
