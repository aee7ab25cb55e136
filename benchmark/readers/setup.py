"""Seconds from process start to the end of warm-up (host clock)."""


def read(ctx):
    return ctx["obs"]["setup_s"]
