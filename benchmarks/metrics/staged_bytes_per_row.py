"""Engine residency: bytes the server holds staged on the device after
the window (``/debug/memory`` ``stagedBytes``) for each row of the table."""


def read(ctx):
    return ctx["after"]["memory"]["stagedBytes"] / ctx["rows"]
