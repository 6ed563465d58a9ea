"""setup_s: seconds from process start to the start of the window:
imports, boot, prefill, failures, warm-up, and on a cold compile
cache the compiles."""


def read(run):
    return run.setup_seconds
