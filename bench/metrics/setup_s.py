"""Set-up seconds: process start to the first due time of the window,
loading, compiling and the warm-up burst included."""


def read(rec):
    return rec.setup_s
