"""Worker daemon: share of each device's EXEC lane busy time spent in the
`input` phase, copying the host input to the device: the rise of the
daemon's cumulative `input_s` gauge over the rise of its `busy_s` gauge
across the window, mean over devices. A daemon that times no phases
reports none."""


def read(run):
    shares = []
    for g in range(run.chips):
        lane = f"worker/w0/gpu{g}/EXEC"
        busy, spent = run.gauge(f"{lane}/busy_s"), run.gauge(f"{lane}/input_s")
        if len(spent) < 2 or len(busy) < 2 or busy[-1][1] <= busy[0][1]:
            return None
        shares.append((spent[-1][1] - spent[0][1])
                      / (busy[-1][1] - busy[0][1]))
    return 100.0 * sum(shares) / len(shares)
