"""Engine residency: of the bytes the server holds staged after the window,
the share on the fullest device (``/debug/memory`` ``devices[].stagedBytes``,
the largest over their sum). 100 over the number of devices is even: 25 on
four chips. Nothing to read from a program without the per-device view."""


def read(ctx):
    devices = ctx["after"]["memory"].get("devices")
    if not devices:
        return None
    staged = [d["stagedBytes"] for d in devices]
    if sum(staged) <= 0:
        return None
    return 100.0 * max(staged) / sum(staged)
