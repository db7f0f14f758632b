"""Forward program (the kernel layer): the least time the chip could take
for the traced window's EXECs over the device time the forward program
took in the trace. Least time per EXEC is counts.roofline_s of its batch
bucket; EXECs are the controller's records that started in the traced
window; device time is the trace's `jit_resnet50_forward` program time."""
import counts
from record import bucket

PROGRAM = "jit_resnet50_forward"


def read(run):
    if run.trace is None or run.peak is None:
        return None
    device_s = run.trace["programs_s"].get(PROGRAM, 0.0)
    execs = run.execs(run.trace_window)
    if device_s <= 0 or not execs:
        return None
    least = sum(counts.roofline_s(bucket(a.batch_size), run.peak,
                                  run.size["img"], run.size["scale"],
                                  run.size["num_classes"]) for a in execs)
    return 100.0 * least / device_s
