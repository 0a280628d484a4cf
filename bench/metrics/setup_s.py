"""Set-up seconds: process start to the first timed operation (generation,
index build, upload, warm-up and any compilation)."""


def read(ctx):
    return ctx["setup_s"]
