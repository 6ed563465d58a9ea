"""tpu_resume_ms.read: mean ms of `tpu_resume` on the critical path,
per client read that reached the device dispatcher (a rebuild the
host's XOR shortcut made never does), benchmark/spans.py."""

from benchmark import spans


def read(run):
    return spans.stage_ms(run, "read", ("tpu_resume",),
                          keep=spans.reached_dispatcher)
