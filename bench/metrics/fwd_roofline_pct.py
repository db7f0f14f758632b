"""Forward program (the kernel layer): the least time the chip could take
for the traced window's EXECs over the device time the forward program
took in the trace. Least time per EXEC is the model's `roofline_s` of its
batch bucket; EXECs are the controller's records that started in the
traced window; device time is the trace's time in the model's `PROGRAM`."""


def read(run):
    if run.trace is None or run.peak is None:
        return None
    device_s = run.trace["programs_s"].get(run.model.PROGRAM, 0.0)
    execs = run.execs(run.trace_window)
    if device_s <= 0 or not execs:
        return None
    least = sum(run.model.roofline_s(run.bucket(a.batch_size), run.peak,
                                     run.size) for a in execs)
    return 100.0 * least / device_s
