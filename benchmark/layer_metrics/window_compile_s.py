"""Seconds inside the backend compiler during the measured window. Should
read 0: work moved from set-up into the window shows here."""


def read(ctx):
    return ctx["window_compile"]["compile_s"]
