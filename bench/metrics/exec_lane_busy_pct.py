"""Worker daemon: share of the window in which each device's EXEC lane was
busy, from the daemon's cumulative `busy_s` gauges (host clock of the
lane's thread), mean over devices."""


def read(run):
    shares = []
    for g in range(run.chips):
        s = run.gauge(f"worker/w0/gpu{g}/EXEC/busy_s")
        if len(s) < 2 or s[-1][0] <= s[0][0]:
            return None
        shares.append((s[-1][1] - s[0][1]) / (s[-1][0] - s[0][0]))
    return 100.0 * sum(shares) / len(shares)
