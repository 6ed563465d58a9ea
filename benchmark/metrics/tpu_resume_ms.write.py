"""tpu_resume_ms.write: mean ms per client write of `tpu_resume` on
its critical path: from the device dispatcher setting the op's event
to the submitting OSD thread running again (osd/tpu_dispatch.py,
benchmark/spans.py)."""

from benchmark import spans


def read(run):
    return spans.stage_ms(run, "write", ("tpu_resume",),
                          keep=spans.reached_dispatcher)
