"""Set-up: seconds from process start to the start of the window."""


def read(rec, tr):
    return rec["setup_s"]
