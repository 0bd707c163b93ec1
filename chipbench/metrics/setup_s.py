"""setup_s: seconds from process start to the window's first due
invocation: weights, image, compile, record-mode invocation, warm-up."""


def read(run):
    return run.setup_s
